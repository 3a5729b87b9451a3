import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Test hook only: integration tests shrink the placeholder device count
# (must happen before jax locks device state on first init).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry run of the paper's workload: .lower().compile() one CoCoA+
round (`configs.paper_svm`) on the production meshes and record its
memory, cost and collective stats.

    PYTHONPATH=src python -m repro.launch.dryrun                # both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --mesh single

Outputs one JSON per mesh under benchmarks/results/dryrun/.
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.launch import mesh as meshlib
from repro.launch.hlo_analysis import full_stats

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / \
    "results" / "dryrun"


def _mesh(name: str):
    if os.environ.get("REPRO_DRYRUN_DEVICES"):
        return (meshlib.make_test_mesh((2, 2), ("data", "model")) if name == "single"
                else meshlib.make_test_mesh((2, 2, 2), ("pod", "data", "model")))
    return meshlib.make_production_mesh(multi_pod=(name == "multi"))


def _mem_dict(compiled):
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        if hasattr(ma, "serialized_size_in_bytes"):
            out["serialized_size_in_bytes"] = int(ma.serialized_size_in_bytes)
        if not out and ma is not None:
            out["repr"] = str(ma)[:2000]
    except Exception as e:            # pragma: no cover
        out["error"] = repr(e)
    return out


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() returns a dict in older jax and a list of
    per-computation dicts in newer versions -- normalize to one dict."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def run_paper_cell(mesh_name: str, outdir: pathlib.Path) -> dict:
    """Dry-run the paper's own workload: one CoCoA+ round on the mesh."""
    from repro.configs.paper_svm import CONFIG as W
    from repro.core.cocoa import CoCoAConfig, CoCoAState, make_round_sharded

    mesh = _mesh(mesh_name)
    # every chip is a CoCoA+ worker (the paper scales in K; Fig. 2)
    daxes = tuple(mesh.axis_names)
    K = mesh.size
    cfg = CoCoAConfig(loss=W.loss, lam=W.lam, gamma=1.0, sigma_p=float(K),
                      H=W.H, backend="shard_map",
                      data_axis=daxes if len(daxes) > 1 else daxes[0])
    nk = W.n // K
    d = W.d
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    state = (sds((d,), f32), sds((K, nk), f32), sds((2,), jnp.uint32),
             sds((), i32), sds((K, nk), f32), sds((K, d), f32))
    X = sds((K, nk, d), f32)
    y = sds((K, nk), f32)
    mask = sds((K, nk), f32)

    round_fn = make_round_sharded(cfg, mesh)

    def step(w, alpha, rng, rounds, abar, ef, X, y, mask):
        st = CoCoAState(w, alpha, rng, rounds, abar, ef)
        st2 = round_fn(st, X, y, mask, n=float(W.n))
        return st2.w, st2.alpha, st2.rounds

    jitted = jax.jit(step)
    t0 = time.time()
    lowered = jitted.lower(*state, X, y, mask)
    compiled = lowered.compile()
    t1 = time.time()
    cost = _cost_dict(compiled)
    st = full_stats(compiled.as_text())
    rec = {
        "arch": "paper-svm", "shape": f"n{W.n}_d{W.d}_H{W.H}",
        "mesh": mesh_name, "kind": "cocoa_round", "compile_s": round(t1 - t0, 2),
        "flops_per_device": st["dot_flops"],
        "hbm_bytes_per_device": st["hbm_bytes"],
        "collectives": st["collectives"],
        "collective_wire_bytes_per_device": st["collective_wire_bytes"],
        "xla_cost_flops": float(cost.get("flops", -1)),
        "memory_analysis": _mem_dict(compiled),
        "n_devices": mesh.size, "K_workers": K,
    }
    out = outdir / f"paper-svm__round__{mesh_name}.json"
    outdir.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    print(f"[ok]   paper-svm round ({mesh_name}): "
          f"flops/dev={rec['flops_per_device']:.3e} "
          f"wire={rec['collective_wire_bytes_per_device']:.3e}B")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_fail = 0
    for mesh_name in meshes:
        try:
            run_paper_cell(mesh_name, outdir)
        except Exception as e:
            n_fail += 1
            print(f"[FAIL] paper-svm ({mesh_name}): {e}")
            traceback.print_exc()
    print(f"done; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
