"""The timed path broken underneath, the rest of a run as it is: the
comparison has to come out false.  One case for each fault a cell can
have: a step that returns its state unchanged (a train step's weights,
a decode step's recurrent state), half of the batch left out with the
mean taken over the rest, and a served token altered where it is
produced.  (The cells run on one chip: no exchange between
chips to leave out.)"""
import pytest

import smoke


def _unchanged(orig):
    def step(self, params, opt_state, batch):
        _, _, metrics = orig(self, params, opt_state, batch)
        return params, opt_state, metrics
    return step


def _half_batch(orig):
    def step(self, params, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, params, opt_state, half)
    return step


def _cache_unchanged(orig):
    def decode_step(self, params, cache, tokens, pos):
        logits, _ = orig(self, params, cache, tokens, pos)
        return logits, cache
    return decode_step


def _token_altered(orig):
    def decode_step(self, params, cache, tokens, pos):
        logits, cache = orig(self, params, cache, tokens, pos)
        # every served token becomes token 7
        return logits.at[:, 7].set(1e4), cache
    return decode_step


FAULTS = {"unchanged": ("repro.runtime.trainer.Trainer", "_train_step",
                        _unchanged),
          "half_batch": ("repro.runtime.trainer.Trainer", "_train_step",
                         _half_batch),
          "token_altered": ("repro.models.lm.LM", "decode_step",
                            _token_altered),
          "cache_unchanged": ("repro.models.lm.LM", "decode_step",
                              _cache_unchanged)}
CASES = [("qwen05b.train_resume", "unchanged"),
         ("qwen05b.train_resume", "half_batch"),
         ("mamba2.serve_snapshot", "token_altered"),
         ("mamba2.serve_snapshot", "cache_unchanged")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    import importlib
    where, attr, plant = FAULTS[fault]
    mod, cls = where.rsplit(".", 1)
    klass = getattr(importlib.import_module(mod), cls)
    monkeypatch.setattr(klass, attr, plant(getattr(klass, attr)))
    out = smoke.execute(cell)
    assert out["correct"] is False, out["checks"]
