"""What the plain reference computes for any model family: a training
run's readings and the gaps of served tokens.

A family's reference is ``bench/reference/<family>.py``, named by the
``reference`` key of a configuration file.  It gives the model's forward
pass and nothing else:

    hidden(params, tokens, c, prec)  final normed hidden states (B, S, d)
                                     of token rows (B, S)
    logits(params, h, c, prec)       logits of hidden states over the
                                     padded vocabulary, padding at -1e30

``prec`` is ``"f32"``, or ``"fp8"`` for the control: every matrix
product's operands rounded to float8 (e4m3, one scale per tensor).
Everything here is plain ``jax.numpy`` in float32; like the references
it imports nothing of the program under test.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from bench import weights as W


# ------------------------------------------------------------ training
def row_nll(ref, params, row, c: Dict[str, Any], prec: str):
    """Summed next-token NLL of one row (T,) over its first T-1 places."""
    import jax
    import jax.numpy as jnp
    lg = ref.logits(params, ref.hidden(params, row[None], c, prec)[0][:-1],
                    c, prec)
    tgt = jnp.take_along_axis(lg, row[1:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(lg, -1) - tgt)


def loss_and_grads(ref, params, tokens, c, prec):
    """Mean NLL over the batch's rows and its gradient, a row at a time."""
    import jax
    import jax.numpy as jnp
    n = tokens.shape[0] * (tokens.shape[1] - 1)

    def body(acc, row):
        loss, g = jax.value_and_grad(functools.partial(row_nll, ref))(
            params, row, c, prec)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, g), _ = jax.lax.scan(body, (jnp.float32(0), zeros), tokens)
    return loss / n, jax.tree.map(lambda x: x / n, g)


def lr_at(step, hp):
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    import jax.numpy as jnp
    base, warm, total = hp["lr"], hp["warmup_steps"], hp["total_steps"]
    frac = jnp.clip((step - warm) / max(1.0, total - warm), 0.0, 1.0)
    lo = hp["min_lr_ratio"]
    cos = lo + (1 - lo) * 0.5 * (1 + jnp.cos(np.pi * frac))
    return jnp.where(step < warm, base * step / max(1.0, warm), base * cos)


def adamw(p, g, m, v, step, hp):
    """One AdamW update with global-norm clipping; ``step`` counts
    from 1."""
    import jax
    import jax.numpy as jnp
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, hp["clip_norm"] / (gnorm + 1e-9))
    t = step.astype(jnp.float32)
    b1, b2 = hp["b1"], hp["b2"]
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    lr = lr_at(t, hp)
    g = jax.tree.map(lambda x: x * scale, g)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
                                  + hp["weight_decay"] * p), p, m, v)
    return p, m, v


def _step(p, m, v, step, tokens, *, ref, c, hp, prec):
    loss, g = loss_and_grads(ref, p, tokens, c, prec)
    p, m, v = adamw(p, g, m, v, step, hp)
    return p, m, v, loss


def train_readings(ref, c: Dict[str, Any], abstract, seed: int,
                   batches: List, hp: Dict[str, Any], *,
                   precision: str = "f32",
                   half_batch: bool = False) -> Dict[str, Any]:
    """Train ``len(batches)`` steps from the seed's weights and return
    the numbers the comparison reads: each step's loss, the per-leaf
    norms of the first gradient as AdamW takes it (its first moment over
    1 - b1, after clipping), and the per-leaf norms of the change of the
    weights over all the steps.

    ``half_batch`` leaves out half of each batch's rows and takes the
    mean over the rest: a fault the comparison has to catch."""
    import jax
    import jax.numpy as jnp
    step = jax.jit(functools.partial(_step, ref=ref, c=c, hp=hp,
                                     prec=precision),
                   donate_argnums=(0, 1, 2))
    p = W.make(abstract, seed)
    m = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(p)
    v = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(p)
    losses, grad1 = [], None
    for i, batch in enumerate(batches, start=1):
        tokens = jnp.asarray(batch["tokens"])
        if half_batch:
            tokens = tokens[: tokens.shape[0] // 2]
        p, m, v, loss = step(p, m, v, jnp.int32(i), tokens)
        losses.append(float(loss))
        if i == 1:
            grad1 = {k: x / (1 - hp["b1"]) for k, x in
                     W.flat_norms(jax.jit(W.leaf_norms)(m)).items()}
    del m, v
    change = W.flat_norms(W.change_norms(p, abstract, seed))
    return {"losses": losses, "grad1": grad1, "change": change}


# ------------------------------------------------------------- serving
def served_gaps(ref, params, tokens, first: int, c: Dict[str, Any]):
    """Per row, the widest gap by which a served token's reference logit
    lies below the reference's best at its place.  Tokens at and after
    index ``first`` of each row are served; the logits at place t judge
    token t+1."""
    import jax
    import jax.numpy as jnp
    h = ref.hidden(params, tokens, c, "f32")

    def row(args):
        h_row, tok = args
        lg = ref.logits(params, h_row[first - 1:-1], c, "f32")
        got = jnp.take_along_axis(lg, tok[first:, None], -1)[:, 0]
        return jnp.max(jnp.max(lg, -1) - got)

    return jax.lax.map(row, (h, tokens))


def control_gaps(ref, params, tokens, first: int, c: Dict[str, Any]):
    """The control's reading: at the same places, the gap of the token
    that the float8 model puts first."""
    import jax
    import jax.numpy as jnp
    h = ref.hidden(params, tokens, c, "f32")
    hq = ref.hidden(params, tokens, c, "fp8")

    def row(args):
        h_row, hq_row = args
        lg = ref.logits(params, h_row[first - 1:-1], c, "f32")
        pick = jnp.argmax(ref.logits(params, hq_row[first - 1:-1], c,
                                     "fp8"), -1)
        got = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return jnp.max(jnp.max(lg, -1) - got)

    return jax.lax.map(row, (h, hq))
