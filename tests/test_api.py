"""repro.api surface: CheckpointOptions validation + env round-trip,
capabilities()/check() report shape, the versioned backend/plugin registry,
the frozen() phase context manager, and session-driven round trips."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CheckpointOptions, CheckpointSession, OptionsError,
                       capabilities, check)
from repro.core import (PLUGIN_API_VERSION, Plugin, PluginVersionError,
                        SnapshotEngine, available_backends, create_backend)
from repro.core.backends import BackendError, register_backend


def make_state(n=3):
    ks = jax.random.split(jax.random.key(0), n)
    return {f"w{i}": jax.random.normal(ks[i], (4, 8), jnp.float32)
            for i in range(n)}


# ---------------------------------------------------------------- options
def test_options_defaults_valid():
    CheckpointOptions().validate()          # must not raise


@pytest.mark.parametrize("bad", [
    dict(mode="turbo"),
    dict(keep=-1),
    dict(lock_timeout_s=0),
    dict(restore_threads=-2),
    dict(replicate_to=""),
])
def test_options_validation_rejects(bad):
    with pytest.raises(OptionsError):
        CheckpointOptions(**bad)


def test_options_frozen():
    o = CheckpointOptions()
    with pytest.raises(dataclasses.FrozenInstanceError):
        o.mode = "async"


def test_options_env_round_trip():
    o = CheckpointOptions(mode="async", incremental=True, compress=True,
                          keep=5, lock_timeout_s=2.5, restore_threads=4,
                          replicate_to="/tmp/peer", verify_restore=False)
    assert CheckpointOptions.from_env(o.to_env()) == o


def test_options_from_env_defaults_and_parsing():
    assert CheckpointOptions.from_env({}) == CheckpointOptions()
    o = CheckpointOptions.from_env({"REPRO_CKPT_MODE": "async",
                                    "REPRO_CKPT_INCREMENTAL": "true",
                                    "REPRO_CKPT_KEEP": "3"})
    assert o.mode == "async" and o.incremental and o.keep == 3


def test_options_replace():
    o = CheckpointOptions().replace(mode="async")
    assert o.mode == "async" and CheckpointOptions().mode == "sync"


# ----------------------------------------------------------- capabilities
def test_capabilities_report_shape():
    caps = capabilities()
    assert caps["plugin_api_version"] == PLUGIN_API_VERSION
    assert caps["jax"]["version"] == jax.__version__
    assert caps["jax"]["device_count"] >= 1
    assert caps["jax"]["platform"] == jax.devices()[0].platform
    assert set(caps["backends"]) >= {"jax", "host"}
    for spec in caps["backends"].values():
        assert spec["api_version"] == PLUGIN_API_VERSION
        assert isinstance(spec["features"], list)
    assert caps["modes"] == ["sync", "async"]


def test_check_passes_here(tmp_path):
    report = check(run_dir=str(tmp_path / "imgs"),
                   options=CheckpointOptions())
    assert report.ok, report.problems
    assert report.capabilities["jax"]["device_count"] >= 1
    assert "repro check" in report.summary()


def test_session_check_and_capabilities(run_dir):
    s = CheckpointSession(run_dir, CheckpointOptions(mode="async"))
    assert s.check().ok
    caps = s.capabilities()
    assert caps["session"]["backend"] == "jax"
    assert caps["session"]["options"]["mode"] == "async"
    assert "device" in caps["session"]["plugins"]


# --------------------------------------------------------------- registry
def test_backend_registry_lists_jax_and_host():
    av = available_backends()
    assert "jax" in av and "host" in av
    assert "device_arrays" in av["jax"]["features"]


def test_backend_registry_rejects_wrong_api_version():
    with pytest.raises(PluginVersionError):
        register_backend("future", lambda **kw: Plugin(),
                         api_version=PLUGIN_API_VERSION + 1)
    assert "future" not in available_backends()


def test_backend_registry_rejects_duplicate_and_unknown():
    with pytest.raises(BackendError):
        register_backend("jax", lambda **kw: Plugin(),
                         api_version=PLUGIN_API_VERSION)
    with pytest.raises(BackendError):
        create_backend("no-such-backend")


def test_engine_rejects_mismatched_plugin(run_dir):
    class OldPlugin(Plugin):
        name = "old"
        api_version = PLUGIN_API_VERSION - 1

    with pytest.raises(PluginVersionError):
        SnapshotEngine(run_dir, plugins=[OldPlugin()])


def test_legacy_engine_kwargs_deprecated(run_dir):
    with pytest.warns(DeprecationWarning):
        eng = SnapshotEngine(run_dir, mode="async", keep=2)
    assert eng.mode == "async" and eng.keep == 2
    # no-kwarg construction stays silent
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SnapshotEngine(run_dir)


# ---------------------------------------------------------------- session
def test_session_round_trip(run_dir, mesh1):
    state = make_state()
    host = {"v": {"step": 3}}
    s = CheckpointSession(run_dir, mesh=mesh1)
    s.attach(lambda: {"train_state": state})
    s.register_host_state("host", lambda: host["v"],
                          lambda v: host.__setitem__("v", v))
    s.checkpoint(3)
    assert s.store.list_steps() == [3]

    host2 = {"v": None}
    s2 = CheckpointSession(run_dir, mesh=mesh1)
    s2.attach(lambda: {"train_state": None})
    s2.register_host_state("host", lambda: None,
                           lambda v: host2.__setitem__("v", v))
    restored = s2.restore()
    assert host2["v"] == {"step": 3}
    np.testing.assert_array_equal(
        np.asarray(restored["train_state"]["w0"]), np.asarray(state["w0"]))


def test_session_frozen_phases(run_dir):
    state = make_state()
    s = CheckpointSession(run_dir)
    s.attach(lambda: {"train_state": state})
    with s.frozen(1) as snap:
        # ①–③ already ran: capture is in host memory, job quiesced
        assert snap.step == 1
        assert "frozen_s" in snap.stats
        assert s.engine.device_plugin.lock.locked
        assert s.store.list_steps() == []      # nothing committed yet
    # ④ ran on exit: image committed, lock released
    assert s.store.list_steps() == [1]
    assert not s.engine.device_plugin.lock.locked
    assert snap.path is not None


def test_session_frozen_abort_on_exception(run_dir):
    s = CheckpointSession(run_dir)
    s.attach(lambda: {"train_state": make_state()})
    with pytest.raises(RuntimeError, match="boom"):
        with s.frozen(1):
            raise RuntimeError("boom")
    assert s.store.list_steps() == []          # no image written
    assert not s.engine.device_plugin.lock.locked


def test_session_frozen_explicit_abort(run_dir):
    s = CheckpointSession(run_dir)
    s.attach(lambda: {"train_state": make_state()})
    with s.frozen(2) as snap:
        snap.abort()                           # e.g. preflight said no
    assert s.store.list_steps() == []
    assert not s.engine.device_plugin.lock.locked


def test_session_host_backend_round_trip(run_dir):
    state = make_state()
    s = CheckpointSession(run_dir, backend="host")
    s.attach(lambda: {"train_state": state})
    s.checkpoint(1)
    s2 = CheckpointSession(run_dir, backend="host")
    s2.attach(lambda: {"train_state": None})
    restored = s2.restore()
    got = restored["train_state"]["w1"]
    assert isinstance(got, np.ndarray)         # never device-placed
    np.testing.assert_array_equal(got, np.asarray(state["w1"]))


def test_session_from_env(run_dir, monkeypatch):
    monkeypatch.setenv("REPRO_CKPT_MODE", "async")
    monkeypatch.setenv("REPRO_CKPT_KEEP", "7")
    s = CheckpointSession.from_env(run_dir)
    assert s.options.mode == "async" and s.options.keep == 7


def test_session_context_manager_waits_async(run_dir):
    state = make_state()
    with CheckpointSession(run_dir, CheckpointOptions(mode="async")) as s:
        s.attach(lambda: {"train_state": state})
        s.checkpoint(1)
    # exiting the with-block drained the background writer
    assert s.store.manifest(1)["step"] == 1


def test_trainconfig_resolves_options():
    from repro.runtime.trainer import TrainConfig
    legacy = TrainConfig(ckpt_mode="async", incremental=True)
    assert legacy.checkpoint_options() == CheckpointOptions(
        mode="async", incremental=True)
    explicit = TrainConfig(ckpt=CheckpointOptions(keep=4))
    assert explicit.checkpoint_options().keep == 4
