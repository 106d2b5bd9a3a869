"""Readers on four device planes, on a hand-made trace: two decode
executions and one admission on each chip, collectives inside and outside
them."""
from types import SimpleNamespace as NS

import pytest

import run
import trace as T
from conftest import BENCH

US = 1000                                   # ns


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * US, duration_ns=dur_us * US,
              stats=list(stats.items()))


def plane(k):
    """Chip k: decode at [1000, 3000) and [5000, 7000) us, admission at
    [8000, 9000); the chips' collectives differ in length by k."""
    mods = [ev("jit_step(7)", 1000, 2000, run_id=1),
            ev("jit_step(7)", 5000, 2000, run_id=2),
            ev("jit_admit(8)", 8000, 1000, run_id=3)]
    ops = [ev("while.4", 1000, 2000),                     # a container
           ev("%all-reduce.12 = bf16[32,1,3584] all-reduce(...)", 1100,
              100 + 10 * k),
           ev("fusion.3", 1300, 500),
           ev("all-gather-start.2", 5100, 40),
           ev("all-gather-done.2", 5200, 60),
           ev("async-collective-done", 5400, 100),
           ev("all-reduce-scatter-fusion.1", 5600, 50),
           ev("psum.3", 5700, 20),                        # in shard_map
           ev("all-reduce.9", 8100, 300),                 # in admission
           ev("psum.4", 8500, 30),
           ev("all-reduce.10", 4000, 300)]                # in no program
    return NS(name=f"/device:TPU:{k}",
              lines=[NS(name=T.MODULES, events=mods),
                     NS(name=T.OPS, events=ops)])


@pytest.fixture(scope="module")
def red():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.engine_step", 0, 10000)])])
    pd = NS(planes=[host] + [plane(k) for k in range(4)])
    return T.reduce(pd, {"decode": "step", "admit": "admit"})


def read(name, red, chips=4):
    return run.load_reader(name, BENCH)(red, {"steps": [], "all_steps": []},
                                        {"chips": chips})


def test_executions_are_counted_once_over_the_chips(red):
    assert red["program_n"] == {"decode": 2, "admit": 1}
    assert red["program_s"]["decode"] == pytest.approx(4e-3)
    assert read("decode_step_ms", red) == pytest.approx(2.0)


def test_collectives_in_decode_per_execution(red):
    # per chip: 100 + 10 k us in the first execution, 40 + 60 + 100 + 50
    # + 20 in the second; mean over k = 0..3 of the sum, over 2 executions
    want = sum(100 + 10 * k + 270 for k in range(4)) / 4 / 2 / 1e3
    assert read("decode_collective_ms", red) == pytest.approx(want)
    ops = red["program_op_s"]
    assert "while" not in ops["decode"]
    assert ops["admit"] == pytest.approx({"all-reduce": 300e-6,
                                          "psum": 30e-6})
    assert ops["decode"]["fusion"] == pytest.approx(500e-6)


def test_no_collective_reads_nothing(red):
    one = dict(red, program_op_s={"decode": {"fusion": 1e-3}})
    assert read("decode_collective_ms", one) is None


def test_combine_keeps_op_seconds_by_program(red):
    both = T.combine([red, red])
    assert both["program_n"]["decode"] == 4
    assert read("decode_collective_ms", both) == pytest.approx(
        read("decode_collective_ms", red))
