"""What the training drivers share: the trainer built as the training
launcher builds it, fed from the benchmark's weights and batches, and
the readings the comparison takes from it."""
from __future__ import annotations

from typing import Any, Dict, List

from bench import judge
from bench import traffic as T
from bench import weights as W


def hyper(t: Dict[str, Any]) -> Dict[str, Any]:
    """The optimizer's settings, as the traffic mix states them."""
    return {k: t[k] for k in ("lr", "warmup_steps", "total_steps",
                              "min_lr_ratio", "b1", "b2", "eps",
                              "weight_decay", "clip_norm")}


def make_trainer(cell, *, ckpt_every: int):
    """A ``Trainer`` as ``repro.launch.train`` makes one, on a mesh of
    the cell's chips, with no state yet."""
    import jax.numpy as jnp
    from repro.api import CheckpointOptions
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.trainer import TrainConfig, Trainer
    from repro.sharding import get_policy

    t = cell.traffic
    cfg = cell.program_config()
    tcfg = TrainConfig(
        batch_size=t["batch"], seq_len=t["seq_len"], lr=t["lr"],
        warmup_steps=t["warmup_steps"], total_steps=t["total_steps"],
        ckpt_every=ckpt_every,
        ckpt=CheckpointOptions(mode=t["ckpt_mode"], keep=t["keep"]),
        compute_dtype=getattr(jnp, t["compute_dtype"]), remat=t["remat"])
    mesh = make_host_mesh(data=cell.chips, model=1)
    tr = Trainer(cfg, tcfg, mesh, get_policy(t["policy"]),
                 str(cell.run_dir / "train"))
    tr.pipeline = T.TokenRows(cell.seed, t["batch"], t["seq_len"],
                              cfg.vocab_size)
    return tr


def load_weights(cell, tr) -> None:
    """The seed's weights and a fresh optimizer state, in place."""
    import jax
    tr.params = W.make(tr.model.init_abstract(), cell.seed,
                       shardings=tr.model.param_shardings())
    tr.opt_state = jax.jit(tr.opt.init,
                           out_shardings=tr._opt_shardings())(tr.params)
    tr.step = 0


def reference(cell, n_steps: int, **kw) -> Dict[str, Any]:
    """The reference's readings over the cell's first ``n_steps``
    batches, from the seed's weights."""
    from repro.models.encdec import build_model  # only for the shapes
    from repro.sharding import get_policy
    t = cell.traffic
    model = build_model(cell.program_config(), get_policy(t["policy"]), None)
    rows = T.TokenRows(cell.seed, t["batch"], t["seq_len"],
                       cell.config["vocab_size"])
    batches = [rows.peek(i) for i in range(n_steps)]
    return judge.train_readings(cell.reference(), cell.config,
                                model.init_abstract(), cell.seed, batches,
                                hyper(t), **kw)


def losses(tr, n: int) -> List[float]:
    return list(tr.metrics_history["loss"][:n])
