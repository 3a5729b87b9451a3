"""The four-chip deployment `rcv1_k4_mesh.epoch` at a tiny size on four
forced CPU host devices (in a subprocess, as `tests/test_sharded.py`
does): the run reads `correct`; with one chip's copy of w perturbed, or
with the exchange skipped, it does not; and the shard_map rounds give the
vmap rounds' answer. Then the three all-reduce readers on synthetic
four-chip traces."""
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from bench import peaks, spec, trace
from bench.tests import tiny

CELL = "rcv1_k4_mesh.epoch"
CHIPS = 4
# as in `tests/test_sharded.py`: a device thread starved on a loaded
# machine aborts the process only well past XLA:CPU's default 40 s
COLLECTIVE_TIMEOUTS = ("--xla_cpu_collective_call_warn_stuck_timeout_seconds"
                       "=120 --xla_cpu_collective_call_terminate_timeout_"
                       "seconds=600")
# shard_map against vmap: the same rounds, the cross-worker sum reduced in
# another order (an all-reduce over devices, one reduction on one device)
PARITY = 1e-6


def _run(code: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={CHIPS} "
                        f"{COLLECTIVE_TIMEOUTS}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(spec.ROOT),
                                         str(spec.ROOT / "src")])
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=str(spec.ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def mesh_tree(dest):
    """The tiny tree with the four-chip cell kept at four devices."""
    root = tiny.tree(dest)
    path = root / "bench" / "configs" / "rcv1_k4_mesh.json"
    cfg = json.loads(path.read_text())
    cfg.update(K=CHIPS, mesh={"data": CHIPS})
    path.write_text(json.dumps(cfg))
    top = json.loads((root / "BENCHMARK.json").read_text())
    for w in top["workloads"]:
        if w["name"] == CELL:
            w["chips"] = CHIPS
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    (root / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps(
        dict(tiny.TINY_CELL, trace_chips=CHIPS)))
    return root


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One subprocess: the sound run, the two faults, and the backends'
    answers after 4 rounds on the cell's tiny data."""
    root = mesh_tree(tmp_path_factory.mktemp("bench"))
    out = _run(f"""
        import dataclasses, json, pathlib, time
        import jax, numpy as np
        from bench import data, harness, spec
        from repro import comm
        from repro.core import solve

        root = pathlib.Path({str(root)!r})
        cell = spec.load_cell({CELL!r}, root=root, bench=root / "bench")

        def run():
            return harness.run(cell, seed=2 ** 33 + 5, seconds=0.2,
                               traced=False, t_start=time.perf_counter())

        def emit(name, value):
            print(json.dumps({{"name": name, "value": value}}))

        out = run()
        emit("sound", {{"correct": out["correct"],
                       "checks": out["checks"],
                       "count": out["device"]["count"]}})

        # one chip's copy of the replicated w, off by 1e-3 of its largest
        solve_ = harness.CellRun.solve

        def perturbed(self, *a, **k):
            sv = solve_(self, *a, **k)
            w = sv.state.w
            shards = [s.data for s in w.addressable_shards]
            bump = 1e-3 * float(np.max(np.abs(np.asarray(w))))
            shards[1] = shards[1].at[0].add(bump)
            sv.state = sv.state._replace(
                w=jax.make_array_from_single_device_arrays(
                    w.shape, w.sharding, shards))
            return sv
        harness.CellRun.solve = perturbed
        out = run()
        harness.CellRun.solve = solve_
        emit("one_copy_off", {{"correct": out["correct"],
                              "checks": out["checks"]}})

        exchange = comm.exchange

        def skipped(topo, du, ef, *a, **k):
            dw, ef = exchange(topo, du, ef, *a, **k)
            return jax.numpy.zeros_like(dw), ef
        comm.exchange = skipped
        out = run()
        comm.exchange = exchange
        emit("no_exchange", {{"correct": out["correct"],
                             "checks": out["checks"]}})

        # the backends: shard_map on the cell's mesh against vmap on one
        # device, 4 rounds from alpha = 0 on the cell's data
        cr = harness.CellRun(cell, seed=11)
        mesh_res = solve(cr.ccfg, cr.X, cr.y, cr.mask, rounds=4, seed=3,
                         mesh=cr.mesh)
        X, y, mask = data.make(cell.config["data"], cr.K, 11)
        vmap_res = solve(dataclasses.replace(cr.ccfg, backend="vmap"),
                         X, y, mask, rounds=4, seed=3)
        emit("parity", {{
            "alpha": float(np.max(np.abs(np.asarray(mesh_res.state.alpha)
                                         - np.asarray(vmap_res.state.alpha)))),
            "w": float(np.max(np.abs(np.asarray(mesh_res.state.w)
                                     - np.asarray(vmap_res.state.w)))),
            "w_scale": float(np.max(np.abs(np.asarray(vmap_res.state.w)))),
            "alpha_scale": float(np.max(np.abs(
                np.asarray(vmap_res.state.alpha)))),
            "gaps": [mesh_res.history["gap"], vmap_res.history["gap"]],
            "alpha_devices": len({{s.device for s in
                                  mesh_res.state.alpha.addressable_shards}}),
        }})
    """)
    return {r["name"]: r["value"] for r in map(json.loads,
                                               out.strip().splitlines())}


def failing(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


def test_sound_mesh_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"] and not failing(r["checks"]), r["checks"]
    assert r["count"] == CHIPS


def test_one_chips_copy_of_w_off_reads_incorrect(runs):
    r = runs["one_copy_off"]
    assert not r["correct"]
    assert "exchange_rel" in failing(r["checks"]), r["checks"]


def test_skipped_exchange_reads_incorrect(runs):
    r = runs["no_exchange"]
    assert not r["correct"]
    assert "exchange_rel" in failing(r["checks"]), r["checks"]


def test_mesh_rounds_match_vmap(runs):
    r = runs["parity"]
    assert r["alpha_devices"] == CHIPS
    assert r["alpha"] <= PARITY * max(1.0, r["alpha_scale"]), r
    assert r["w"] <= PARITY * max(1.0, r["w_scale"]), r
    np.testing.assert_allclose(*r["gaps"], rtol=PARITY)


# ----------------------------------------------------------------------------
# the readers, on synthetic traces
# ----------------------------------------------------------------------------

SCOPE = "jit(round_fn)/shard_map/cocoa/exchange/all_reduce/psum"
OTHER = "jit(round_fn)/shard_map/cocoa/local_solve/while/body/gather"
D = 47_236


def chip(all_reduce_s, solve_s=1.0):
    """A chip whose traced round is a local solve and then the
    all-reduce, back to back."""
    names = [("jit_round_fn", "fusion.1", "fusion", OTHER),
             ("jit_round_fn", "all-reduce.1", "all-reduce", SCOPE)]
    return trace.Chip(start=np.array([0.0, solve_s]),
                      end=np.array([solve_s, solve_s + all_reduce_s]),
                      label=np.array([0, 1]), names=names)


def ctx_of(chips, chips_of_cell=CHIPS, peak=True):
    reduced = trace.Reduced(chips=chips, busy_s=1.0, window_s=1.0,
                            top_ops=[], idle=[])
    cell = types.SimpleNamespace(chips=chips_of_cell,
                                 config={"data": {"d": D}})
    return types.SimpleNamespace(
        cell=cell, trace=reduced,
        peak=peaks.peak_of("TPU v5 lite") if peak else None)


ARS = [40e-6, 55e-6, 100e-6, 45e-6]
FOUR = [chip(s) for s in ARS]


def test_all_reduce_ms_is_the_least_chips_time():
    got = spec.load_reader("all_reduce_ms")(ctx_of(FOUR))
    assert got == pytest.approx(1e3 * min(ARS))


def test_all_reduce_wait_ms_is_the_mean_less_the_least():
    read = spec.load_reader("all_reduce_wait_ms")
    assert read(ctx_of(FOUR)) == pytest.approx(
        1e3 * (np.mean(ARS) - min(ARS)))
    even = read(ctx_of([chip(50e-6)] * CHIPS))
    assert even == pytest.approx(0.0, abs=1e-12)


def test_all_reduce_roofline_is_ring_bytes_over_the_link():
    got = spec.load_reader("all_reduce_roofline")(ctx_of(FOUR))
    # a ring over 4 chips: 2 * 3/4 of d float32 through each chip's link
    ring = 2 * 3 / 4 * 4 * D
    assert got == pytest.approx(100 * ring / 200e9 / min(ARS))
    assert 0 < got < 100


@pytest.mark.parametrize("metric", ["all_reduce_ms", "all_reduce_wait_ms",
                                    "all_reduce_roofline"])
def test_readers_read_none_without_a_collective(metric):
    read = spec.load_reader(metric)
    # one chip: no collective to time
    assert read(ctx_of([chip(50e-6)], chips_of_cell=1)) is None
    # four chips, but no instruction under the scope (the parent's round)
    bare = trace.Chip(start=np.array([0.0]), end=np.array([1.0]),
                      label=np.array([0]),
                      names=[("jit_round_fn", "psum.6", "all-reduce",
                              "jit(round_fn)/shard_map/cocoa/exchange/psum")])
    assert read(ctx_of([bare] * CHIPS)) is None
    # no trace at all (an untraced run)
    untraced = ctx_of([chip(50e-6)] * CHIPS)
    untraced.trace = None
    assert read(untraced) is None


def test_roofline_reads_none_without_a_peak():
    assert spec.load_reader("all_reduce_roofline")(
        ctx_of(FOUR, peak=False)) is None


def test_the_mesh_configuration_is_its_own_deployment():
    # a configuration with another's source and cuts is the same deployment
    top = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in top["configs"]}
    name, = [w["config"] for w in top["workloads"] if w["name"] == CELL]
    mesh = configs.pop(name)
    assert all((c["source"], c["reduced"]) != (mesh["source"], mesh["reduced"])
               for c in configs.values())
    assert 1 <= len(mesh["source"]) <= 200
    assert json.loads((spec.ROOT / mesh["file"]).read_text())["source"] \
        == mesh["source"]
