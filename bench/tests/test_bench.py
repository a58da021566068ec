"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They cover the trace reducer on a profile JAX writes, on a trace
recorded on a TPU v5e and on a hand-made device trace, the work counts
against hand counts, the on-device generator against the repository's
k-NN graph and the twin's statistics, the request schedule's fixed
work, the loading of every cell by name, the refusal to run without a
chip, the correctness check on sound runs, the control (the
reference at the precision below the configuration's) failing it, and
each fault of the timed path failing it.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import deploy, reference, spec, trace_reduce, traffic, workcount  # noqa: E402

SMALL_N = 600


def small_cell(name: str, n: int = SMALL_N):
    cell = spec.resolve(spec.load(ROOT), name, ROOT)
    cell.cfg["n_users"] = n
    if cell.traffic.get("requests"):
        cell.traffic["requests"]["rate_per_s"] = 25.0
    return cell


# -- the trace reducer --------------------------------------------------------


def test_reducer_reads_a_profiler_trace(tmp_path):
    """A real profile written by JAX: the traced window's host span is
    found and cut, and the trace directory is removed. (The host has no
    TPU plane, so no device is read.)"""
    import jax
    import jax.numpy as jnp

    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        with jax.profiler.TraceAnnotation("bench.train_call"):
            jax.jit(lambda x: x * 2.0)(jnp.ones(8)).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    kept = tmp_path / "events.json.gz"
    red = trace_reduce.reduce_dir(str(trace_dir), keep=str(kept))
    assert not trace_dir.exists()
    assert red["window_s"] >= 0.01
    assert red["devices"] == {} and red["busy_s"] == 0
    assert any(name == "bench.train_call" for name, *_ in red["host_spans"])
    assert trace_reduce.load(str(kept))


def test_reducer_reads_scopes_from_the_traces_hlo(tmp_path):
    """The trace keeps each program's HLO; an instruction's scope is the
    ``obs.<scope>`` of its ``op_name``, and an instruction without one
    takes the scope of the root of the computation it calls."""
    import glob

    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("obs.gather_mix"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("obs.row_update"):
            return jnp.cos(y) + 1.0

    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    jax.jit(step)(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        protos = trace_reduce.hlo_protos(f.read())
    (name,) = [k for k in protos if k.startswith("jit_step")]
    scopes = trace_reduce.instruction_scopes(protos[name])
    found = {scope for _, scope in scopes.values()}
    assert {"gather_mix", "row_update"} <= found
    assert all(opcode for opcode, _ in scopes.values())


def test_reducer_on_a_recorded_v5e_trace(tmp_path):
    """One 16-slot chunk of the serve cell's super-tick program, recorded
    on a TPU v5e (the trace cut to that program's ops, its HLO and the
    driving thread's spans): every super-tick span is found, the
    ``while`` that holds the slots is left out, and the spans fit in the
    program's device time."""
    import gzip

    path = tmp_path / "chunk.xplane.pb"
    with gzip.open(DATA / "tpu_v5e_supertick.xplane.pb.gz", "rb") as f:
        path.write_bytes(f.read())
    evs = trace_reduce.events(str(path))
    assert not any(e["n"].startswith("while") for e in evs)
    red = trace_reduce.reduce(evs)
    dev = red["devices"][0]
    (program,) = dev["module_s"]
    assert "_chunk_impl" in program
    assert dev["module_s"][program] == pytest.approx(0.0726208, rel=1e-4)
    assert {"wake_sample", "gather_mix", "row_update", "scatter"} <= set(dev["scope_s"])
    assert dev["scope_s"]["gather_mix"] == pytest.approx(0.0265185, rel=1e-4)
    assert sum(dev["scope_s"].values()) < dev["busy_s"] <= dev["module_s"][program]


def test_reducer_busy_spans_and_gaps():
    ms = 1_000_000
    evs = [
        {"d": -1, "k": "host", "n": "bench.traced_window", "m": "main", "s": "", "t": 0, "u": 10 * ms},
        {"d": -1, "k": "host", "n": "bench.train_call", "m": "main", "s": "", "t": 0, "u": 9 * ms},
        {"d": 0, "k": "module", "n": "jit__chunk_impl", "m": "jit__chunk_impl", "s": "", "t": 1 * ms, "u": 5 * ms},
        {"d": 0, "k": "op", "n": "fusion.1", "m": "jit__chunk_impl", "s": "gather_mix", "t": 1 * ms, "u": 2 * ms},
        {"d": 0, "k": "op", "n": "fusion.2", "m": "jit__chunk_impl", "s": "row_update", "t": 2 * ms, "u": 2 * ms},
        {"d": 0, "k": "op", "n": "fusion.3", "m": "jit__chunk_impl", "s": "scatter", "t": 5 * ms, "u": 1 * ms},
    ]
    red = trace_reduce.reduce(evs)
    dev = red["devices"][0]
    assert red["window_s"] == pytest.approx(0.010)
    assert dev["busy_s"] == pytest.approx(0.004)  # [1, 4) and [5, 6) ms
    assert dev["scope_s"]["gather_mix"] == pytest.approx(0.002)
    assert dev["scope_s"]["scatter"] == pytest.approx(0.001)
    assert dev["module_s"]["jit__chunk_impl"] == pytest.approx(0.005)
    gaps = [g for _, g in red["breakdown"]["idle_gaps"]]
    assert gaps == pytest.approx([0.004, 0.001, 0.001])
    assert "bench.train_call" in red["breakdown"]["idle_gaps"][0][0]


# -- work counts ---------------------------------------------------------------


def test_work_counts_by_hand():
    w = workcount.per_update(np.array([3.0]), np.array([2.0]), 4)
    # Neighbours: 2 rows of 4 floats; own data: 3 points of 4 + 1 floats
    # and the own row of 4; the row written back: 4 floats.
    assert w["mix_bytes"][0] == 4 * 2 * 4
    assert w["row_bytes"][0] == 4 * (3 * 4 + 3 + 4)
    assert w["bytes"][0] == 4 * (2 * 4 + 3 * 5 + 4 + 4)
    assert w["mix_flops"][0] == 2 * 2 * 4
    assert w["row_flops"][0] == 3 * (7 * 4 + 2) + 8 * 4
    mean = workcount.rate_weighted_mean(
        workcount.per_update(np.array([1.0, 3.0]), np.array([1.0, 1.0]), 1), np.array([1.0, 3.0])
    )
    assert mean["row_bytes"] == pytest.approx(0.25 * 4 * 3 + 0.75 * 4 * 7)


# -- the generator ---------------------------------------------------------------


@pytest.fixture(scope="module")
def small_dep():
    cfg = dict(small_cell("movielens-p20-100k.serve").cfg)
    return deploy.generate(cfg, 2**31 + 5, block=256)


def test_generator_statistics(small_dep):
    d = small_dep
    cfg = d.cfg
    assert d.counts.min() >= cfg["count_min"] and d.counts.max() <= cfg["count_max"]
    m = d.mask.sum(axis=1)
    assert np.array_equal(m, np.maximum(np.floor(cfg["train_frac"] * d.counts), 1))
    assert np.array_equal(m + d.test_count, d.counts)
    # The twin's law: lognormal(4.35, 0.8) clipped to 20-737 has a mean
    # near 106 (MovieLens-100K's); 600 users put it within 15%.
    assert 90 < d.counts.mean() < 125
    # Items are distinct within a user, and centred ratings sum to zero.
    for u in range(20):
        items = np.concatenate([d.train_items[u, : int(m[u])], d.test_items[u, : d.test_count[u]]])
        assert np.unique(items).size == items.size
        assert abs(d.y[u].sum()) < 1e-3
    assert np.all(d.train_items[d.mask == 0] == cfg["n_items"])


def test_generator_knn_matches_repository_graph(small_dep):
    from repro.core.graph import knn_graph

    d = small_dep
    # The generator keeps no rating vectors, so its k-NN search is checked
    # against the repository's on vectors of the same kind (sparse stars).
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    vecs = (rng.random((300, 50)) < 0.2) * rng.integers(1, 6, (300, 50)).astype(np.float64)
    mine = deploy.knn_lists(jnp.asarray(vecs, jnp.float32), 10, 64)
    ref = knn_graph(vecs, k=10)
    unit = vecs / np.maximum(np.linalg.norm(vecs, axis=1, keepdims=True), 1e-300)
    sim = unit @ unit.T
    np.fill_diagonal(sim, -np.inf)
    kth = -np.sort(-sim, axis=1)[:, 9]
    for i in range(vecs.shape[0]):
        # Same selection up to ties at the k-th similarity.
        assert np.all(sim[i, mine[i]] >= kth[i] - 1e-6)
    # OR-symmetrised: the same undirected graph wherever there is no tie.
    n = vecs.shape[0]
    rows = np.repeat(np.arange(n), 10)
    from repro.core.graph import csr_from_coo

    g = csr_from_coo(n, rows, mine.ravel(), np.ones(n * 10), symmetrize=True)
    same = np.mean([
        set(g.neighbors(i).tolist()) == set(ref.neighbors(i).tolist()) for i in range(n)
    ])
    assert same > 0.95
    assert d.graph().n == d.n


def test_seed_permutes_the_same_population():
    cfg = dict(small_cell("movielens-p20-100k.serve", n=300).cfg)
    a = deploy.generate(cfg, 1, block=128)
    b = deploy.generate(cfg, 2, block=128)
    assert np.array_equal(np.sort(a.counts), np.sort(b.counts))
    assert not np.array_equal(a.counts, b.counts)
    da = np.bincount(reference.dep_edges(a)[0], minlength=a.n)
    db = np.bincount(reference.dep_edges(b)[0], minlength=b.n)
    assert np.array_equal(np.sort(da), np.sort(db))


def test_every_seed_offers_the_same_requests_in_another_order():
    cfg = dict(small_cell("movielens-p20-100k.serve", n=300).cfg)
    mix = small_cell("movielens-p20-100k.serve").traffic
    scheds = []
    for seed in (4, 2**31 + 11):
        d = deploy.generate(cfg, seed, block=128)
        scheds.append(traffic.schedule(mix, d.counts, d.test_count, seed, 4.0))
    a, b = scheds
    assert len(a) == len(b) == round(mix["requests"]["rate_per_s"] * 4.0)
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    assert np.allclose(np.sort(np.diff(a.due_s, prepend=0.0)), np.sort(np.diff(b.due_s, prepend=0.0)))
    assert not np.array_equal(a.sizes, b.sizes)
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] <= 4.0 + 1e-9


# -- the harness and its entry point ------------------------------------------


def test_every_cell_resolves_by_name():
    bench = spec.load(ROOT)
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"], ROOT)
        traffic.check(cell.traffic)
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def _run_cli(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "movielens-p20-100k.serve",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def _harness_run(cell, seconds=1.0):
    import jax

    from bench import harness

    return harness.run(cell, 2**31 + 21, seconds, False, jax.devices()[: cell.chips],
                       time.perf_counter())


def test_sound_run_is_correct():
    out = _harness_run(small_cell("movielens-p20-100k.serve"))
    assert out["correct"], out["checks"]
    assert out["checks"]["wake_set_diff"]["value"] == 0
    assert out["values"]["updates_per_s"] > 0
    assert np.isfinite(out["values"]["predict_p95_ms"])


def test_control_fails_the_limits():
    """The reference at the precision below the configuration's (bf16 x3
    products for f32 at full precision) breaks one of the limits, and so
    does the reference leaving out half of each slot's batch."""
    from bench import control

    out = control.readings(small_cell("movielens-p20-100k.serve"), 2**31 + 9, slots=128,
                           seconds=1.0)
    for name in ("control", "half_batch"):
        nums = out[name]
        assert any(v["value"] > v["limit"] for v in nums.values()), (name, nums)


# -- faults of the timed path ------------------------------------------------


def _fault_state_unchanged(mp):
    from repro.sim import engine

    mp.setattr(engine.AsyncEngine, "advance", _unchanged(engine.AsyncEngine.advance))


def _unchanged(advance):
    """The run driver's step computing the new state and returning the old
    one. (Not in the chunk: set-up compiles the chunk itself to ask for
    its tables' layouts, which a chunk that ignores its tables does not
    give.)"""
    import jax

    def step(self, state, slots):
        jax.block_until_ready(advance(self, state, slots))
        return state

    return step


def _fault_half_batch(mp):
    import jax.numpy as jnp

    from repro.sim import updates

    orig = updates.CDUpdate.apply_rows

    def half(self, *a, **k):
        rows, valid, st = orig(self, *a, **k)
        # Half of the woken rows: the batch is padded past them.
        keep = jnp.cumsum(valid) <= valid.sum() // 2
        return rows, valid & keep, st

    mp.setattr(updates.CDUpdate, "apply_rows", half)


def _fault_answer_altered(mp):
    from repro.serve import handle

    orig = handle._score_rows
    mp.setattr(handle, "_score_rows", lambda *a: orig(*a).at[0].add(1e-3))


@pytest.mark.parametrize(
    "fault", [_fault_state_unchanged, _fault_half_batch, _fault_answer_altered],
    ids=["state_unchanged", "half_batch", "answer_altered"],
)
def test_fault_of_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _harness_run(small_cell("movielens-p20-100k.serve"))
    assert not out["correct"], out["checks"]
