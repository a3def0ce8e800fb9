"""The readers of the restore, jit, wait and decode-host metrics, on
spans made by hand: what each sums, what it leaves out, and that it
reads nothing from a program that has no such span."""
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


@pytest.mark.parametrize("metric,span", [
    ("restore_verify_s", "restore.verify"),
    ("restore_read_s", "restore.read"),
    ("restore_place_s", "restore.place"),
    ("resume_wait_s", "train.sync"),
])
def test_mean_per_resume_of_spans_begun_in_the_window(metric, span):
    run = view([(span, 10.0, 90.0),            # set-up: before the window
                ("restore.critical", 120.0, 124.0),
                (span, 120.0, 121.0),
                (span, 150.0, 153.0),
                (span, 199.5, 201.0)])         # begun inside, ends after
    assert read(metric, run) == pytest.approx((1.0 + 3.0 + 1.5) / 3)


def test_resume_jit_merges_nested_phases_and_divides_by_resumes():
    run = view([
        ("jit.trace", 50.0, 60.0),             # warm-up resume: set-up
        # first resume: an inner trace inside the outer one, then lower
        # and a compile-cache load
        ("jit.trace", 110.0, 110.2),
        ("jit.trace", 110.05, 110.1),
        ("jit.lower", 110.2, 110.45),
        ("jit.compile", 110.45, 110.6),
        # second resume: the same program again
        ("jit.trace", 160.0, 160.2),
        ("jit.lower", 160.2, 160.4),
        ("jit.compile", 160.4, 160.5)], work={"resumes": 2})
    assert read("resume_jit_s", run) == pytest.approx((0.6 + 0.5) / 2)


def test_decode_host_ms_is_step_less_its_sync_per_token():
    spans = []
    for i in range(4):
        t = 120.0 + i * 0.0145
        spans += [("serve.step", t, t + 0.0145, {"pos": i}),
                  ("serve.sync", t + 0.002, t + 0.0125)]
    spans.append(("serve.step", 90.0, 90.1, {"pos": -1}))   # set-up
    spans.append(("dump.write", 130.0, 140.0))
    got = read("decode_host_ms.serve", view(spans))
    assert got == pytest.approx(1e3 * (0.0145 - 0.0105))


@pytest.mark.parametrize("metric", [
    "restore_verify_s", "restore_read_s", "restore_place_s",
    "resume_jit_s", "resume_wait_s", "decode_host_ms.serve"])
def test_a_program_without_the_spans_reads_nothing(metric):
    run = view([("restore.critical", 120.0, 124.0),
                ("resume.first_step", 124.0, 126.0),
                ("dump.write", 130.0, 140.0)], work={"resumes": 3})
    assert read(metric, run) is None


def test_resume_jit_reads_nothing_without_a_resume():
    run = view([("jit.compile", 120.0, 121.0)], work={"resumes": 0})
    assert read("resume_jit_s", run) is None
