"""Kill and resume: the restore path alone, with no save in the window.

Set-up trains ``resume_at`` steps from the seed's weights, saves the
state (asynchronously, as the program does, then waits for the write)
and runs one step more, uninterrupted, for the loss a resume must
reproduce.  It then resumes once, to warm every program the resume
runs.  The window repeats what a restarted process does: the previous
incarnation is dropped, a fresh ``Trainer`` (new session, new jit) is
built, ``restore()`` reads and verifies the image and places it, and
one step runs to completion.

``resume_s`` is the window over the number of resumes it completed.
The page cache holds the image: a restart on a new host reads it cold.
"""
from __future__ import annotations

import gc
import time

from bench import compare, weights as W
from bench import train_common as C
from bench.harness import Check, Window

KIND = "train"


def _resume(cell):
    tr = C.make_trainer(cell, ckpt_every=0)
    with cell.span("resume.restore"):
        step = tr.restore()
    with cell.span("resume.first_step"):
        tr.run_until(step + 1)          # returns once the loss is on the host
    return tr, step


def setup(cell):
    at = cell.traffic["resume_at"]
    with cell.span("setup.build"):
        tr = C.make_trainer(cell, ckpt_every=at)
        C.load_weights(cell, tr)
    with cell.span("setup.first_steps"):
        tr.run_until(at)                # saves at `at`
    with cell.span("setup.save_write"):
        tr.session.wait_pending()
    with cell.span("setup.first_steps"):
        tr.run_until(at + 1)
    first = C.losses(tr, at + 1)
    del tr
    gc.collect()
    with cell.span("setup.warm_resume"):
        tr, _ = _resume(cell)
    return {"trainer": tr, "first": first, "resumes": []}


def window(state, cell) -> Window:
    t0 = time.perf_counter()
    deadline = t0 + cell.seconds
    resumes = state["resumes"]
    each = {"resume_s_each": [], "restore_s_each": [],
            "first_step_s_each": []}
    t1 = t0
    while t1 < deadline:
        state["trainer"] = None         # the kill: no copy stays alive
        gc.collect()
        state["trainer"], step = _resume(cell)
        resumes.append((step, state["trainer"].metrics_history["loss"][-1]))
        t, t1 = t1, time.perf_counter()
        each["resume_s_each"].append(t1 - t)
        for name, key in (("resume.restore", "restore_s_each"),
                          ("resume.first_step", "first_step_s_each")):
            s = next(s for s in reversed(cell.spans) if s[0] == name)
            each[key].append(s[2] - s[1])
    n = len(resumes)
    return Window(metrics={"resume_s": (t1 - t0) / n},
                  units={"resume_s": "s"}, attempted=n, failed=0,
                  t0=t0, t1=t1, work={"resumes": n, **each})


def drain(state, cell) -> None:
    pass


def check(state, cell):
    tr = state.pop("trainer")
    at = cell.traffic["resume_at"]
    first = state["first"]
    # every resume restored step `at` and reproduced the uninterrupted
    # loss of step at+1 bit for bit
    off = sum(1 for step, loss in state["resumes"]
              if step != at or loss != first[at])
    prog = {"losses": first[:at] + [tr.metrics_history["loss"][-1]],
            "change": W.flat_norms(W.change_norms(
                tr.params, tr.model.init_abstract(), cell.seed))}
    del tr
    gc.collect()
    ref = C.reference(cell, at + 1)
    prog["grad1"] = ref["grad1"]        # not read in this cell
    return compare.train_checks(prog, ref, with_grad=False) + [
        Check("resumes_off", float(off), 0.0)]
