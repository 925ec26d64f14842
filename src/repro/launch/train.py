"""Production training launcher: MeSP LoRA fine-tuning with the full
substrate — sharded step, restartable data, atomic checkpoints, straggler
watchdog. On this container it runs real steps on small configs
(``--reduced``) and is the same code path the dry-run lowers for the
production mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-0.5b \\
        --reduced --steps 100 --engine mesp --ckpt-dir /tmp/run1

The CLI is generated from ``repro.api``: ``--engine`` choices come from the
engine registry (registering a new engine adds it here with no edits to this
file) and the whole invocation round-trips through
:class:`repro.api.TrainSpec`. All run mechanics live in the
:class:`repro.api.Trainer` facade.
"""
from __future__ import annotations

import logging

from repro import telemetry
from repro.api import Trainer, TrainSpec
from repro.launch.compile_cache import enable_compile_cache
# re-exported: scripts/check_readme_flags.py and tests import the parser
# from here, its historical home
from repro.api import build_arg_parser  # noqa: F401

log = logging.getLogger("repro.train")


def main(argv=None):
    spec = TrainSpec.from_cli_args(argv).validate()
    enable_compile_cache()

    logging.basicConfig(
        level=logging.WARNING if spec.quiet else logging.INFO)
    trainer = Trainer.from_spec(spec)
    cfg = trainer.cfg
    log.info("arch=%s layers=%d d_model=%d engine=%s quantize=%s",
             cfg.name, cfg.n_layers, cfg.d_model, spec.engine, spec.quantize)

    result = trainer.fit()
    # end-of-run reporting goes through the structured choke point
    # (repro.telemetry): per-step lines already did during fit
    telemetry.log_run_summary(result, quiet=spec.quiet)
    if result.degradations:
        fs = result.final_spec
        log.info("final spec after degradation: engine=%s batch=%d "
                 "seq=%d quantize=%s", fs.engine, fs.batch, fs.seq,
                 fs.quantize)
    if spec.telemetry == "on":
        log.info("telemetry: %s", result.metrics.get("telemetry_dir"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
