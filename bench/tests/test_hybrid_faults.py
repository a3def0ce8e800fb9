"""The granite cell's decode path broken underneath, the rest of a run
as it is: the comparison has to come out false (the faults of
``test_faults.py``, planted in the hybrid replica's decode step)."""
import pytest

import smoke
from test_faults import FAULTS


@pytest.mark.parametrize("fault", ["token_altered", "cache_unchanged"])
def test_hybrid_fault_is_not_correct(fault, monkeypatch):
    import importlib
    where, attr, plant = FAULTS[fault]
    mod, cls = where.rsplit(".", 1)
    klass = getattr(importlib.import_module(mod), cls)
    monkeypatch.setattr(klass, attr, plant(getattr(klass, attr)))
    out = smoke.execute("granite4h.serve_longgen")
    assert out["correct"] is False, out["checks"]
