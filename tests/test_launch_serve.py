"""The serving launcher end to end on the CPU at smoke width: query
strings in, one response each, and a non-zero exit when the executor
had to rescue a batch from the prior."""
import numpy as np
import pytest

from repro.launch import serve as S

ARGV = ["--drain-mode", "fused", "--corpus", "512", "--index-shards",
        "4", "--n-requests", "8"]


def test_main_serves_query_strings_and_exits_zero(capsys):
    assert S.main(ARGV) == 0
    out = capsys.readouterr().out
    assert "[smoke width]" in out
    assert "retrieval: " in out and "FAIL" not in out


def test_second_pass_over_the_same_queries_reads_the_trust_db():
    run = S.launch(S.parse_args(ARGV))
    queries = S.serve(run)
    assert len(queries) == 8 and len(run.eng.completed) == 8
    S.serve(run, queries)
    second = run.eng.completed[8:]
    assert len(second) == 8
    assert sum(r.shed.n_cached for r in second) > 0
    assert S.report(run) == 0


@pytest.mark.parametrize("fail_in", ["dispatch_staged", "_finish"])
def test_rescued_batch_fails_the_run(fail_in):
    """A kernel or evaluator that raises on the device is answered
    from the prior (no request is dropped) — and the launcher must
    then exit non-zero instead of printing a healthy scoreboard."""
    run = S.launch(S.parse_args(ARGV))
    shedder = run.eng.replicas[0].engine.shedder

    def boom(*args, **kwargs):
        raise RuntimeError("injected device failure")

    setattr(shedder, fail_in, boom)
    S.serve(run)
    assert len(run.eng.completed) == 8               # still answered
    assert all(r.reason.startswith("executor_error")
               for r in run.eng.completed if r.admitted)
    assert np.all([(r.tier == 2).all() for r in run.eng.completed])
    assert S.report(run) == 1
