"""Emulated-fleet runs of the ``moe`` family under a data-parallel mesh.

Each case spawns a ``launch/fleet.py`` worker with two emulated host
devices (see ``test_fleet_equivalence.py``). A step returns the expert
counters beside the loss only where the engine computes gradients; the
Trainer's jitted step has to take either. Under a mesh the ``mesp``
engine's expert layer runs the capacity path (explicit sharding), while
``mesp_pallas`` runs each data shard's batch on the dropless path inside
its ``shard_map`` and sums the shards' counters.
"""
import functools
import json

import numpy as np
import pytest

from repro.launch.fleet import run_fleet

BASE = {"arch": "olmoe-1b-7b", "reduced": True, "batch": 4, "seq": 32,
        "seed": 5, "optimizer": "sgd"}
STEPS = 2


@functools.lru_cache(maxsize=None)
def _train(spec_json: str, devices: int):
    return run_fleet({"task": "train", "spec": json.loads(spec_json),
                      "steps": STEPS}, devices=devices)


def _run(engine, devices):
    return _train(json.dumps(dict(BASE, engine=engine), sort_keys=True),
                  devices)


@pytest.mark.parametrize("engine", ["mezo", "mesp", "mesp_pallas"])
def test_moe_step_runs_under_a_data_parallel_mesh(engine):
    res = _run(engine, 2)
    assert res["devices"] == 2 and res["mesh"]["data"] == 2
    assert len(res["losses"]) == STEPS
    assert all(np.isfinite(res["losses"]))
    c = res["counters"]
    if engine == "mezo":
        # the zeroth-order step returns no counters
        assert c == {}
        return
    # slots a step routes: top-2 at each of the 2 layers
    rows = STEPS * BASE["batch"] * BASE["seq"] * 2 * 2
    if engine == "mesp_pallas":
        # dropless on each shard: every slot routed and computed
        assert c["routed_rows"] == rows and c["dropped"] == 0
        assert c["computed_rows"] >= rows
    else:
        # capacity path: every expert's buffer of C rows per sequence
        assert c["routed_rows"] == rows
        assert 0 <= c["dropped"] < rows


@pytest.mark.parametrize("engine", ["mezo", "mesp_pallas"])
def test_moe_mesh_matches_one_device(engine):
    """The same dispatch on one device and split over two data shards:
    the losses agree, and so do the rows routed and dropped (each shard
    pads its own tiles, so the rows computed may differ)."""
    one, two = _run(engine, 1), _run(engine, 2)
    assert one["mesh"] == {}
    np.testing.assert_allclose(two["losses"], one["losses"], rtol=1e-5)
    for k in ("routed_rows", "dropped"):
        assert two["counters"].get(k) == one["counters"].get(k)
