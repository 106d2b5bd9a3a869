"""Window statistics: every due request counts, nothing is a median of
chunks, and gaps and tokens are cut at the window's edges."""
from types import SimpleNamespace as NS

import pytest

import run


def handle(due, toks):
    return (NS(t_submit=due, t_tokens=list(toks), output=list(toks)), None)


def test_unfinished_request_counts_its_wait():
    rec = {"t0": 0.0, "t_end": 10.0, "handles": [
        handle(1.0, [1.5, 1.6, 1.7]),     # TTFT 0.5
        handle(8.0, []),                  # no token by the end: >= 2.0
        handle(9.0, [10.5]),              # first token after the end: 1.0
        handle(10.0, [])]}                # due at the close: not in window
    s = run.window_stats(rec)
    assert sorted(s["ttft"]) == pytest.approx([0.5, 1.0, 2.0])


def test_gaps_and_tokens_cut_at_the_edges():
    rec = {"t0": 10.0, "t_end": 20.0, "handles": [
        handle(5.0, [9.0, 10.5, 12.0, 19.5, 20.5])]}
    s = run.window_stats(rec)
    # the gap 9.0 -> 10.5 ends inside; 19.5 -> 20.5 ends after the close
    assert s["itl"] == pytest.approx([1.5, 1.5, 7.5])
    assert s["tokens"] == 3
    assert s["window_s"] == 10.0


def test_e2e_metrics_units():
    rec = {"t0": 0.0, "t_end": 2.0, "handles": [
        handle(0.0, [0.1, 0.2, 0.4]), handle(0.5, [0.7, 0.8])]}
    m = run.e2e_metrics(rec, 42.0, ["ttft_p90_ms", "itl_p50_ms",
                                    "output_tok_s", "setup_s"])
    assert m["output_tok_s"] == {"value": 2.5, "unit": "tokens/s"}
    assert m["setup_s"]["value"] == 42.0
    assert m["itl_p50_ms"]["value"] == pytest.approx(100.0)
    assert m["ttft_p90_ms"]["unit"] == "ms"


class FakeEngine:
    """Just enough of ServingEngine for the window loop: each step emits one
    token to every live request and takes ``dt`` seconds."""

    def __init__(self, dt=0.01):
        import time
        self.dt, self.B, self.live, self.sleep = dt, 4, [], time.sleep
        self.scheduler = NS(pending=0, active=0)

    def compile_counts(self):
        return {"prefill": 1, "decode": 1}

    @property
    def has_work(self):
        return bool(self.live)

    def submit(self, req):
        h = NS(request=req, output=[], t_tokens=[], done=False, t_submit=0.0)
        self.live.append(h)
        return h

    def step(self):
        import time
        self.sleep(self.dt)
        for h in self.live:
            h.output.append(1)
            h.t_tokens.append(time.perf_counter())
            h.done = len(h.output) >= h.request.max_new_tokens
        self.live = [h for h in self.live if not h.done]
        self.scheduler.active = len(self.live)
        return 1


def test_stretches_spread_over_the_window(tmp_path, monkeypatch):
    import numpy as np
    import traffic
    monkeypatch.setattr(run, "gen_request",
                        lambda r, i: NS(prompt=r.prompt,
                                           max_new_tokens=r.max_new))
    reqs = [traffic.Request(0.0, np.zeros(4, np.int32), 10 ** 6)]
    cell = {"budget": 1.0, "trace": {"stretch_s": 0.1, "stretches": 3}}
    rec = run.run_window(FakeEngine(), reqs, cell, 1.0, tmp_path)
    assert run.stretch_starts(1.0, 3) == pytest.approx([0.1, 0.1 + 0.8 / 3,
                                                        0.1 + 1.6 / 3])
    marks = [s["stretch"] for s in rec["steps"]]
    assert sorted({m for m in marks if m is not None}) == [0, 1, 2]
    # each stretch is one run of consecutive steps
    runs = [m for i, m in enumerate(marks)
            if m is not None and (i == 0 or marks[i - 1] != m)]
    assert runs == [0, 1, 2]
    assert sorted(p.name for p in tmp_path.iterdir()) == ["0", "1", "2"]
    assert all(list(p.glob("**/*.xplane.pb")) for p in tmp_path.iterdir())


def test_profiler_pauses_stop_the_window_clock(tmp_path, monkeypatch):
    import time
    import jax
    import numpy as np
    import traffic
    stop = jax.profiler.stop_trace

    def slow_stop():
        stop()
        time.sleep(0.3)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    monkeypatch.setattr(run, "gen_request",
                        lambda r, i: NS(prompt=r.prompt,
                                        max_new_tokens=r.max_new))
    reqs = [traffic.Request(0.0, np.zeros(4, np.int32), 10 ** 6)]
    cell = {"budget": 1.0, "trace": {"stretch_s": 0.05, "stretches": 2}}
    rec = run.run_window(FakeEngine(), reqs, cell, 1.0, tmp_path)
    # two stretches written out, 0.3 s each, do not count against the 1 s
    assert rec["t_end"] - rec["t0"] >= 1.0 + 2 * 0.3
    served = rec["steps"][-1]["t1"] - rec["steps"][0]["t0"]
    assert served >= 1.0 + 2 * 0.3 - 0.05
