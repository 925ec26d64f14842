"""A training run whose timed path is broken comes out not correct, and the
int8 control is told apart from the bf16 program (tiny cell, CPU)."""
import time

import pytest

SEED = 5001


def test_sound_run_is_correct(tiny_root, no_chip):
    from bench import run

    line = run.run_cell("tiny.ft", SEED, 1, False, root=tiny_root)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "peak_hbm_gb",
                                    "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tiny_root, no_chip, fault):
    from bench import run

    line = run.run_cell("tiny.ft", SEED, 1, False, root=tiny_root,
                        fault=fault)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("seed", [5006, 5007, 5008])
def test_int8_control_is_not_correct(tiny_root, no_chip, seed):
    from bench import check, spec
    from bench.drivers import train

    bench = spec.load_benchmark(tiny_root)
    got = train.run(conf=spec.load_config(bench, "tiny", tiny_root),
                    traffic=spec.load_traffic("tiny.ft", tiny_root),
                    seed=seed, seconds=0, quantize="int8",
                    t_start=time.time())
    correct, checks = check.judge(got["verify"](),
                                  spec.load_limits("tiny.ft", tiny_root))
    assert not correct, checks


@pytest.mark.parametrize("seed", [5006, 5007, 5008])
def test_int8_reference_control_is_not_correct(tiny_root, no_chip, seed):
    """The reference on an int8-rounded base, put in the program's place."""
    from bench import check, spec
    from bench.drivers import train

    bench = spec.load_benchmark(tiny_root)
    got = train.run(conf=spec.load_config(bench, "tiny", tiny_root),
                    traffic=spec.load_traffic("tiny.ft", tiny_root),
                    seed=seed, seconds=0, t_start=time.time())
    correct, checks = check.judge(got["verify"](control=True),
                                  spec.load_limits("tiny.ft", tiny_root))
    assert not correct, checks
