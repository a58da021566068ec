"""The benchmark on the sharded engine: CPU only, four host devices, small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_sharded_check.py -q

One child process, with four forced host devices, drives whole harness
runs of a configuration whose ``engine`` block names the sharded engine
(S = 4, RCM relabel, point-to-point f32 halo) under a mix that serves no
requests: sound runs on three seeds, and runs with each fault of the
timed path the cell can have. The test process itself stays at one
device and checks the refusals of :func:`bench.harness.check_engine`,
the placement check of the reference, the control's readings under
per-shard clocks, and that the single-device configuration builds the
same ``EngineConfig`` as before the engine block existed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness, reference, spec, workcount  # noqa: E402

SMALL_N = 600
SHARDS = 4
SOUND_SEEDS = (2**31 + 101, 2**31 + 102, 2**31 + 103)
SERVE = "movielens-p20-100k.serve"
ENGINE = {
    "kind": "sharded",
    "partition_mode": "degree",
    "relabel": "rcm",
    "exchange": {"method": "p2p", "dtype": "f32", "error_feedback": False},
}
# A training mix with no requests: 1% of the users woken per slot.
TRAIN = {"clock_rate": 1.0, "slot_wakes_per_agent": 0.01, "slots_per_call": 16}
CHILD_TIMEOUT_S = 900


def sharded_cell(n: int = SMALL_N) -> spec.Cell:
    base = spec.resolve(spec.load(ROOT), SERVE, ROOT)
    cfg = dict(base.cfg, n_users=n, engine=json.loads(json.dumps(ENGINE)))
    return spec.Cell("sharded.train", SHARDS, cfg, dict(TRAIN), base.end_to_end, [])


# -- the child: whole runs on four host devices ---------------------------------


def _swap_two_shards(orig):
    def placement_of(engine):
        placement, facts = orig(engine)
        if placement is not None:
            placement = placement[[1, 0] + list(range(2, len(placement)))]
        return placement, facts

    return placement_of


def _fault(name: str):
    """The patch that plants fault ``name`` in the timed path."""
    import jax.numpy as jnp

    from repro.sim import engine, updates

    if name == "state_unchanged":
        from bench.tests.test_bench import _unchanged

        return mock.patch.object(engine.ShardedAsyncEngine, "advance",
                                 _unchanged(engine.ShardedAsyncEngine.advance))
    if name == "half_batch":
        orig = updates.CDUpdate.apply_rows

        def half(self, *a, **k):
            rows, valid, st = orig(self, *a, **k)
            # Half of this shard's woken rows: the batch is padded past them.
            keep = jnp.cumsum(valid) <= valid.sum() // 2
            return rows, valid & keep, st

        return mock.patch.object(updates.CDUpdate, "apply_rows", half)
    if name == "placement_swapped":
        return mock.patch.object(harness, "placement_of", _swap_two_shards(harness.placement_of))
    return contextlib.nullcontext()


def _child() -> int:
    """Runs every scenario and prints one JSON object of their readings."""
    from repro.launch.runtime import force_host_devices

    force_host_devices(SHARDS)
    # Under a loaded host a device thread can reach XLA:CPU's collective
    # rendezvous later than its default 40 s termination timeout.
    os.environ["XLA_FLAGS"] += " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    import jax

    from bench import control, deploy, traffic

    devices = jax.devices()[:SHARDS]
    cell = sharded_cell()
    seen = {}
    orig = harness.placement_of

    def spy(engine):
        placement, facts = orig(engine)
        seen["engine_batch"] = int(engine.batch_size)
        seen["placement"] = placement
        return placement, facts

    out = {}
    runs = [("sound", s) for s in SOUND_SEEDS]
    runs += [(f, SOUND_SEEDS[0]) for f in ("state_unchanged", "half_batch", "placement_swapped")]
    for name, seed in runs:
        with mock.patch.object(harness, "placement_of", spy), _fault(name):
            res = harness.run(cell, seed, 1.0, False, devices, time.perf_counter())
        prob = reference.wake_probability(traffic.slot_wakes(cell.traffic, SMALL_N), SMALL_N)
        dep = deploy.generate(cell.cfg, seed)
        out[f"{name}:{seed}"] = {
            "correct": res["correct"],
            "checks": res["checks"],
            "window": res["window"],
            "engine_batch": seen["engine_batch"],
            "reference_batch": reference.shard_batch(prob, seen["placement"], SMALL_N),
            "control_placement_same": bool(np.array_equal(
                control.engine_placement(cell.cfg, dep, SHARDS), seen["placement"])),
        }
    print("RESULT " + json.dumps(out), flush=True)
    return 0


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=CHILD_TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_sound_sharded_runs_are_correct(runs):
    for seed in SOUND_SEEDS:
        r = runs[f"sound:{seed}"]
        assert r["correct"], (seed, r["checks"])
        assert r["checks"]["wake_set_diff"]["value"] == 0
        assert r["window"]["applied"] > 0 and r["window"]["compiles"] == 0
        facts = r["window"]["placement"]
        assert facts["shards"] == SHARDS and facts["exchange_method"] == "p2p"
        assert facts["rows_per_shard"] >= SMALL_N // SHARDS
        assert 0.0 < facts["halo_fraction"] < 1.0 and facts["exchange_rows_per_slot"] > 0
        assert workcount.halo_bytes_per_slot(facts, 20) == facts["exchange_rows_per_slot"] * 80


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "placement_swapped"])
def test_sharded_fault_is_not_correct(runs, fault):
    r = runs[f"{fault}:{SOUND_SEEDS[0]}"]
    assert not r["correct"], r["checks"]


def test_reference_batch_is_the_engines(runs):
    for key, r in runs.items():
        assert r["reference_batch"] == r["engine_batch"], key


def test_control_cuts_the_graph_as_the_engine(runs):
    assert all(r["control_placement_same"] for r in runs.values())


# -- in this process ------------------------------------------------------------


@pytest.mark.parametrize("engine, chips, words", [
    (dict(ENGINE, exchange={"method": "p2p", "dtype": "bf16"}), 4, "quantised"),
    (dict(ENGINE, exchange={"method": "p2p", "dtype": "int8", "error_feedback": True}), 4,
     "quantised"),
    (dict(ENGINE, shards=4), 4, "unknown key"),
    (dict(ENGINE, exchange={"method": "p2p", "wire": "f32"}), 4, "unknown key"),
    (dict(ENGINE, relabel="sfc"), 4, "coordinates"),
    (ENGINE, 1, "one chip"),
    ({"kind": "async"}, 4, "one device"),
    ({"kind": "async", "relabel": "rcm"}, 1, "sharded"),
    ({"kind": "pipelined"}, 1, "known"),
], ids=["bf16", "int8", "unknown_key", "unknown_exchange_key", "sfc", "sharded_one_chip",
        "async_four_chips", "async_with_placement", "unknown_kind"])
def test_check_engine_refuses(engine, chips, words):
    with pytest.raises(ValueError, match=words):
        harness.check_engine({"engine": engine}, chips)


def test_check_engine_takes_both_kinds():
    assert harness.check_engine({}, 1) == {"kind": "async"}
    assert harness.check_engine({"engine": {"kind": "async"}}, 1) == {"kind": "async"}
    assert harness.check_engine({"engine": ENGINE}, 4) == ENGINE


def test_serve_configuration_builds_the_same_engine_config():
    """The single-device cell's ``EngineConfig``, field by field, is the one
    the harness built before configurations could name an engine."""
    import jax

    from bench import deploy, traffic
    from repro.sim import EngineConfig

    cell = spec.resolve(spec.load(ROOT), SERVE, ROOT)
    devices, n, seed = jax.devices()[:1], int(cell.cfg["n_users"]), 2**31 + 7
    ecfg, shards = harness.engine_config(cell.cfg, cell.traffic, n, seed, devices)
    before = EngineConfig(
        slot_wakes=traffic.slot_wakes(cell.traffic, n),
        rates=float(cell.traffic["clock_rate"]),
        seed=deploy.engine_seed(seed),
        devices=list(devices),
    )
    assert shards is None
    for f in dataclasses.fields(EngineConfig):
        assert getattr(ecfg, f.name) == getattr(before, f.name), f.name
    sharded, s = harness.engine_config(sharded_cell().cfg, TRAIN, SMALL_N, seed, devices * 4)
    assert s == 4 and sharded.relabel == "rcm" and sharded.partition_mode == "degree"
    assert sharded.exchange.method == "p2p" and sharded.exchange.dtype == "f32"


def _placement(n=12, S=3, R=5):
    place = np.full((S, R), n, np.int32)
    ids = np.random.default_rng(0).permutation(n)
    place[0, :4], place[1, :5], place[2, :3] = ids[:4], ids[4:9], ids[9:]
    return place


@pytest.mark.parametrize("bad", ["duplicate", "missing", "out_of_range", "negative",
                                 "wrong_shards", "float"])
def test_placement_that_is_not_one_raises(bad):
    place = _placement()
    shards = 3
    if bad == "duplicate":
        place[2, 0] = place[0, 0]
    elif bad == "missing":
        place[2, 0] = 12
    elif bad == "out_of_range":
        place[2, 4] = 13
    elif bad == "negative":
        place[2, 4] = -1
    elif bad == "wrong_shards":
        shards = 4
    else:
        place = place.astype(np.float32)
    assert reference.check_placement(_placement(), 12, 3).shape == (3, 5)
    with pytest.raises(ValueError, match="placement"):
        reference.check_placement(place, 12, shards)


def test_replay_refuses_a_malformed_placement():
    from bench import deploy

    cell = sharded_cell(n=200)
    dep = deploy.generate(cell.cfg, 5, block=128)
    place = np.arange(200, dtype=np.int32).reshape(4, 50)
    place[3, 49] = 0
    theta0 = harness.initial_theta(5, dep.n, dep.p)
    with pytest.raises(ValueError, match="exactly once"):
        reference.replay(dep, cell.cfg, theta0, 1, np.float32(0.01), 16, 16,
                         np.zeros(1, np.int32), "highest", placement=place, shards=4)


def test_sharded_control_fails_the_limits():
    """Under per-shard clocks, the reference leaving out half of each
    shard's woken rows breaks a limit (the placement comes from the
    engine's cut, on the host, so this runs on one device)."""
    from bench import control

    out = control.readings(sharded_cell(), 2**31 + 9, slots=128, seconds=1.0)
    nums = out["half_batch"]
    assert any(v["value"] > v["limit"] for v in nums.values()), nums


if __name__ == "__main__":
    sys.exit(_child())
