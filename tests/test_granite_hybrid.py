"""granite-4.0-h-small (hybrid Mamba-2 + NoPE attention + MoE with a
shared expert, muP multipliers) against its plain reference,
``bench/reference/granite_hybrid.py``, on seeded random weights at the
benchmark configuration's smoke sizes (``bench/configs``: one period of
ten layers, every width shrunk; the cut's routing kept: 72 routed
experts, top-10, 9 held).

The comparisons are of logits, never of sampled tokens alone.  Each
tolerance is relative to the largest reference logit and says why it is
what it is; computing the program's matrix products in bfloat16 instead
of the configuration's float32 fails them.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import weights as W
from bench.reference import granite_hybrid as ref
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import moe as MOE
from repro.models.encdec import build_model
from repro.models.layers import mlp
from repro.runtime.server import DecodeServer, SequenceFull
from repro.sharding import get_policy

POLICY = get_policy("baseline")
# f32 program against the f32 reference: the chunked SSD scan, the
# cached decode and XLA's reduction orders differ from the reference's
# step-by-step sums by ~1e-6 of the logits' scale (9.3e-7 measured);
# 1e-4 leaves a hundredfold margin.  bf16 matrix products miss by 2e-2.
LOGIT_TOL = 1e-4
# one MoE layer's output, fewer sums than a whole model: ~1e-7
LAYER_TOL = 1e-5


def spec(**over):
    """The benchmark's configuration file at its smoke sizes, with
    ``over`` on top: the reference's config (published keys)."""
    s = harness.load_json("configs", "granite-4.0-h-small")
    return dict(s, **{**s["smoke"], **over})


def _server(spec_, tmp_path, compute=jnp.float32, held=jnp.float32,
            max_seq=64, name="srv", model=None):
    return DecodeServer(harness.program_config(spec_), POLICY,
                        make_host_mesh(1, 1), str(tmp_path / name),
                        max_seq=max_seq, compute_dtype=compute,
                        param_dtype=held, model=model)


def _prompt(spec_, B=2, S=12, seed=4):
    rng = np.random.default_rng(seed)
    return rng.integers(0, spec_["vocab_size"], (B, S), dtype=np.int32)


def _greedy(srv, params, prompt, n):
    """Prefill, then n greedy steps through the padded cache, with the
    server's own programs: the tokens (B, S+n+1) and the logits that
    chose tokens S..S+n (B, n+1, V)."""
    S = prompt.shape[1]
    logits, cache = srv._prefill(params, {"tokens": jnp.asarray(prompt)})
    cache = srv._pad_cache(cache, srv.max_seq)
    out, toks = [logits], [jnp.argmax(logits, -1).astype(jnp.int32)]
    for i in range(n):
        logits, cache = srv._decode(params, cache, toks[-1],
                                    jnp.int32(S + i))
        out.append(logits)
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
    tokens = np.concatenate([prompt, np.stack(toks, 1)], axis=1)
    return tokens, np.stack([np.asarray(x, np.float32) for x in out], 1)


@pytest.mark.parametrize("compute,agrees", [(jnp.float32, True),
                                            (jnp.bfloat16, False)],
                         ids=["f32", "bf16_matmuls"])
def test_prefill_then_decode_matches_the_reference(compute, agrees,
                                                   tmp_path):
    """Prefill 12 tokens, decode 20 more through the cache (positions
    12-31 of a 64-row cache: every step writes into the padding), and
    compare every step's logits with the reference's full forward pass
    over the same tokens.  The f32 program agrees within LOGIT_TOL; the
    same program with its matrix products in bf16 does not."""
    s = spec()
    srv = _server(s, tmp_path, compute=compute)
    params = W.make(srv.model.init_abstract(), 11)
    prompt = _prompt(s)
    tokens, got = _greedy(srv, params, prompt, 20)
    h = ref.hidden(params, jnp.asarray(tokens), s)
    want = np.asarray(ref.logits(params, h[:, 11:32], s))
    V = s["vocab_size"]
    err = np.max(np.abs(got[..., :V] - want[..., :V]))
    scale = np.max(np.abs(want[..., :V]))
    assert (err <= LOGIT_TOL * scale) == agrees, (err, scale)


def test_expert_shares_sum_to_the_uncut_layer():
    """The deployment's eight chips of nine experts each: the parts that
    each share's layer computes (its held experts of the top-10 of all
    72), with the shared expert counted once, add up to the reference's
    layer that holds all 72.  Chip c holds experts 9c..9c+8: its
    router's columns are rolled so they come first, which relabels the
    experts and changes no choice."""
    s = spec(num_local_experts=72)
    cfg = harness.program_config(s)
    params = W.make(build_model(cfg, POLICY, None).init_abstract(), 5)
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"]["moe"])
    v = jax.random.normal(jax.random.key(2), (24, cfg.d_model))
    share_cfg = dataclasses.replace(cfg, moe_experts_held=9)
    got = mlp(moe["shared"], v, POLICY)
    for c in range(8):
        share = {"router": jnp.roll(moe["router"], -9 * c, axis=1),
                 **{k: moe[k][9 * c:9 * c + 9]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, _ = MOE.moe_block(share, share_cfg, v[None], POLICY, None,
                             dropless=True)
        got = got + y[0]
    want = ref._moe(v, moe, s, "f32")
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= LAYER_TOL * float(jnp.max(jnp.abs(want))), err


def test_prefill_routes_dropless():
    """With a capacity factor that drops most assignments in a training
    forward pass, the serving prefill still agrees with the reference
    (which drops nothing) at every layer's routing: its last logits match,
    and the training pass's do not."""
    s = spec()
    cfg = dataclasses.replace(harness.program_config(s),
                              moe_capacity_factor=0.1)
    model = build_model(cfg, POLICY, None, compute_dtype=jnp.float32,
                        remat=False)
    params = W.make(model.init_abstract(), 6)
    tokens = jnp.asarray(_prompt(s, S=16))
    V = s["vocab_size"]
    want = np.asarray(ref.logits(params, ref.hidden(params, tokens, s)[:, -1],
                                 s))[:, :V]
    scale = np.max(np.abs(want))
    prefill, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
    dropped = jax.jit(model.forward)(params, {"tokens": tokens})[:, -1]
    err = lambda lg: np.max(np.abs(np.asarray(lg)[:, :V] - want))
    assert err(prefill) <= LOGIT_TOL * scale
    assert err(dropped) > 10 * LOGIT_TOL * scale


def test_cold_restore_of_a_bf16_replica_is_token_exact(tmp_path):
    """A replica that holds and computes in bf16 (as the benchmark's):
    snapshot mid-generation, restore cold into a fresh server (no load,
    no prefill), continue token-exact; the image holds bf16 weights and
    the cold server's template is built in bf16."""
    s = spec()
    bf = jnp.bfloat16
    srv = _server(s, tmp_path, compute=bf, held=bf)
    srv.load(W.make(srv.model.init_abstract(), 7, dtype=bf))
    srv.start({"tokens": _prompt(s)})
    srv.decode(3)
    srv.checkpoint(0)
    expected = srv.decode(4).copy()

    cold = _server(s, tmp_path, compute=bf, held=bf)
    assert cold.params is None
    cold.restore()
    assert {str(p.dtype) for p in jax.tree.leaves(cold.params)} == \
        {"bfloat16"}
    assert cold._compute_params() is cold.params       # nothing to cast
    assert cold.pos == srv.pos - 4
    np.testing.assert_array_equal(cold.decode(4), expected)


@pytest.mark.parametrize("arch,bounded", [("granite-4.0-h-small", True),
                                          ("mamba2-2.7b", False)])
def test_decode_past_max_seq(arch, bounded, tmp_path):
    """A model with full attention raises before a step would write a
    key/value row at max_seq (the write would be clamped onto the last
    row); a recurrent model's state has no rows and decodes on."""
    cfg = get_smoke_config(arch)
    srv = DecodeServer(cfg, POLICY, make_host_mesh(1, 1),
                       str(tmp_path / "srv"), max_seq=10)
    srv.load(srv.model.init(jax.random.key(0)))
    srv.start({"tokens": np.zeros((1, 8), np.int32)})
    srv.decode(2)                                    # positions 8 and 9
    if bounded:
        with pytest.raises(SequenceFull):
            srv.decode(1)
        assert srv.pos == 10
    else:
        srv.decode(3)
        assert srv.pos == 13


# (full config, smoke config): sha256 of every (path, shape, dtype) of the
# parameter tree, recorded before granite-4.0-h-small was added
TREES = {
    "phi3-medium-14b": ("fb3cd79cae92436d", "36f8fdfa64443111"),
    "deepseek-coder-33b": ("49c8afc4ec755ade", "36f8fdfa64443111"),
    "h2o-danube-1.8b": ("fff2c2e5bf321645", "36f8fdfa64443111"),
    "qwen1.5-0.5b": ("91ce6bc27b197c21", "e48734261e1b780a"),
    "jamba-v0.1-52b": ("fed75dcc2ae86368", "18e839e307874328"),
    "whisper-tiny": ("46c476646cc5e729", "e17870d345ad3e4a"),
    "mamba2-2.7b": ("730080b649fdc332", "17d152d207b33336"),
    "qwen3-moe-30b-a3b": ("0d84e63b0ff5734a", "ace8150f466d8e28"),
    "qwen3-moe-235b-a22b": ("4fb0035aae20e87e", "ace8150f466d8e28"),
    "qwen2-vl-7b": ("e1f8a1fc3f588a38", "d0beef3749df397f"),
}


def _tree_digest(cfg):
    tree = build_model(cfg, POLICY, None).init_abstract()
    text = "\n".join(f"{jax.tree_util.keystr(p)} {tuple(a.shape)} {a.dtype}"
                     for p, a in jax.tree_util.tree_flatten_with_path(tree)[0])
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("arch", sorted(TREES))
def test_existing_parameter_trees_are_unchanged(arch):
    """The new config fields default to what every other architecture
    built before: the same leaves, shapes and dtypes."""
    assert arch in ARCH_IDS
    assert (_tree_digest(get_config(arch)),
            _tree_digest(get_smoke_config(arch))) == TREES[arch]
