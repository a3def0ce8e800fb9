"""The readers of the granite cell's per-layer metrics, on spans and
trace summaries made by hand (what each reads, and that each reads
nothing where its run holds nothing), and the decode server's gauges
and the engine's capture counter they stand on."""
import pytest

from bench import harness

T0, T1 = 100.0, 200.0          # the window, on the spans' clock


def view(spans, work=None):
    cell = harness.Cell(name="stub", workload={}, traffic={}, config={},
                        seed=0, seconds=T1 - T0, trace=True, run_dir=None,
                        spans=[(s[0], s[1], s[2], s[3] if len(s) > 3 else {})
                               for s in spans])
    win = harness.Window(metrics={}, units={}, attempted=0, failed=0,
                         t0=T0, t1=T1, work=dict(work or {}))
    return harness.RunView(cell=cell, window=win, profile=None, peaks=None,
                           chips=1, tracer=None)


def read(metric, run):
    return harness.load_module("metrics", metric).read(run)


HYBRID = "granite4h.serve_longgen"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def hybrid_view(spans=(), work=None, profile=None, peaks=PEAKS):
    run = view(spans, work)
    w = harness.find_workload(HYBRID)
    run.cell.config, run.cell.traffic = w["config_spec"], w["traffic_spec"]
    run.profile, run.peaks = profile, peaks
    return run


def _least_s(pos):
    """The cell's least step time at a position, from its reference."""
    w = harness.find_workload(HYBRID)
    ref, c = harness.reference(w["config_spec"]), w["config_spec"]
    return max(ref.decode_flops(c, 8, pos) / PEAKS["bf16_flops"],
               ref.decode_bytes(c, 8, pos) / PEAKS["hbm_bytes_per_s"])


def test_hybrid_decode_is_memory_bound_and_grows_with_position():
    w = harness.find_workload(HYBRID)
    ref, c = harness.reference(w["config_spec"]), w["config_spec"]
    # the program's 2,054,955,392 held bf16 parameters, f32 state and
    # bf16 tails read and written, 2 x 8 x 128 bf16 per key/value row per
    # request: at position 0, one row read and one written
    state = 9 * 8 * (128 * 64 * 128 * 4 + 3 * (8192 + 256) * 2)
    row = 8 * 2 * 8 * 128 * 2
    assert ref.param_count(c) == 2_054_955_392
    assert ref.decode_bytes(c, 8, 0) == 2 * 2_054_955_392 + 2 * state + 2 * row
    assert ref.decode_bytes(c, 8, 100) - ref.decode_bytes(c, 8, 0) == 100 * row
    assert ref.decode_flops(c, 8, 0) / PEAKS["bf16_flops"] < \
        ref.decode_bytes(c, 8, 0) / PEAKS["hbm_bytes_per_s"] / 10


def test_hybrid_mfu_and_roofline_read_the_window_mean_position():
    work = {"decode_steps": 4000, "positions": [2064, 6064]}
    least = _least_s((2064 + 6063) / 2)
    run = hybrid_view(work=work, profile={
        "modules": {"jit_decode_step": (32.0, 4000.0)},
        "busy_s": 40.0, "window_s": 50.0})
    assert harness.load_module("metrics", "mfu.decode.hybrid").read(run) == \
        pytest.approx(100.0 * least * 4000 / (T1 - T0))
    assert harness.load_module("metrics", "decode_step_roofline.hybrid"
                               ).read(run) == pytest.approx(
        100.0 * least / (32.0 / 4000))
    assert read("device_idle.decode.hybrid", run) == pytest.approx(20.0)


def test_hybrid_frozen_time_and_capture_rate_per_snapshot():
    spans = [("dump.pause", 130.0, 130.01, {"step": 5000}),
             ("dump.capture", 130.01, 131.01, {"step": 5000,
                                               "bytes": 4.95e9}),
             ("dump.ext_state", 131.01, 131.02, {"step": 5000}),
             ("dump.capture", 20.0, 22.0, {"step": 1, "bytes": 1.0})]
    run = hybrid_view(spans)
    assert read("dump_frozen_ms.hybrid", run) == pytest.approx(1020.0)
    assert read("capture_gbps.hybrid", run) == pytest.approx(4.95)


@pytest.mark.parametrize("metric", [
    "mfu.decode.hybrid", "decode_step_roofline.hybrid",
    "device_idle.decode.hybrid", "dump_frozen_ms.hybrid",
    "capture_gbps.hybrid"])
def test_hybrid_metrics_read_nothing_where_there_is_nothing(metric):
    """No positions (a driver without them), no trace, no snapshot, or a
    capture span without its byte count (a program before the counter):
    the metric is left out of the line."""
    spans = ([("dump.capture", 130.0, 131.0, {"step": 9})]
             if metric == "capture_gbps.hybrid" else [])
    run = hybrid_view(spans, work={"decode_steps": 10})
    assert read(metric, run) is None


def test_serve_gauges_and_capture_counter(tmp_path):
    """The decode server gauges its weights and its cache by layer kind
    at load, prefill and restore; each frozen capture counts its bytes
    and gives them to its span."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.runtime.server import DecodeServer
    from repro.sharding import get_policy
    cfg = get_smoke_config("granite-4.0-h-small")
    mk = lambda: DecodeServer(cfg, get_policy("baseline"),
                              make_host_mesh(1, 1), str(tmp_path / "s"),
                              max_seq=32, compute_dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    reg, tracer = obs_metrics.MetricsRegistry(), obs_trace.Tracer()
    obs_metrics.install(reg)
    obs_trace.install(tracer)
    try:
        srv = mk()
        srv.load(srv.model.init(jax.random.key(0)))
        nbytes = lambda t: sum(x.nbytes for x in jax.tree.leaves(t))
        assert reg.gauges == {"serve.bytes.params": nbytes(srv.params)}
        srv.start({"tokens": np.zeros((2, 8), np.int32)})
        attn = [f"pos{j}" for j, k in enumerate(cfg.layer_pattern)
                if k == "attn"]
        kv = nbytes({j: srv.cache[j] for j in attn})
        assert kv == 2 * 2 * 2 * 32 * cfg.num_kv_heads * cfg.head_dim
        assert reg.gauges["serve.bytes.kv"] == kv
        assert reg.gauges["serve.bytes.state"] == nbytes(srv.cache) - kv
        srv.checkpoint(0)
        srv.session.wait_pending()
        want = nbytes(srv.params) + nbytes(srv.cache)
        assert reg.counters["dump.captured_bytes"] == want
        (cap,) = [s for s in tracer.spans if s.name == "dump.capture"]
        assert cap.attrs["bytes"] == want
        reg.gauges.clear()
        cold = mk()
        cold.restore()
        assert reg.gauges == {"serve.bytes.params": nbytes(srv.params),
                              "serve.bytes.kv": kv,
                              "serve.bytes.state": nbytes(srv.cache) - kv}
    finally:
        obs_trace.uninstall()
        obs_metrics.uninstall()
