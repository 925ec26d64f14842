"""A serving run that alters a served token comes out not correct, and the
int8 control's picks are told apart (tiny cell, CPU)."""
import time

SEED = 6001
SEED_CONTROL = 6002


def test_sound_serving_run_is_correct(tiny_root, no_chip):
    from bench import run

    line = run.run_cell("tiny.serve", SEED, 1, False, root=tiny_root)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "peak_hbm_gb",
                                    "setup_s"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_altered_token_is_not_correct(tiny_root, no_chip):
    from bench import run

    line = run.run_cell("tiny.serve", SEED, 1, False, root=tiny_root,
                        fault="token")
    assert not line["correct"], line["checks"]


def test_int8_control_is_not_correct(tiny_root, no_chip):
    from bench import check, spec
    from bench.drivers import serve

    bench = spec.load_benchmark(tiny_root)
    got = serve.run(conf=spec.load_config(bench, "tiny", tiny_root),
                    traffic=spec.load_traffic("tiny.serve", tiny_root),
                    seed=SEED_CONTROL, seconds=1, t_start=time.time())
    correct, checks = check.judge(got["verify"](control=True),
                                  spec.load_limits("tiny.serve", tiny_root))
    assert not correct, checks
