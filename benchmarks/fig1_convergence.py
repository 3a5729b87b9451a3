"""Paper Figure 1: duality gap vs communicated vectors, CoCoA vs CoCoA+,
across regularization lambda and local-iteration count H.

Offline stand-ins replace covtype/RCV1 (benchmarks run with scaled-down n/d;
the qualitative claims under test: (i) CoCoA+ (adding) beats CoCoA
(averaging) everywhere, (ii) the advantage grows with larger lambda and
smaller H -- both visible in the paper's grid."""
from __future__ import annotations

import numpy as np

from repro.core import CoCoAConfig, solve
from repro.data import load, partition
from repro.launch.compile_cache import enable_compile_cache

from .common import Timer, maybe_plot, save


def run(quick: bool = True):
    datasets = [("covtype_like", 4)] if quick else [("covtype_like", 4),
                                                    ("rcv1_like", 8)]
    lams = [1e-4, 1e-5] if quick else [1e-4, 1e-5, 1e-6]
    Hs = [100, 1000] if quick else [100, 1000, 10000]
    rounds = 25 if quick else 60
    out = []
    for ds, K in datasets:
        X, y = load(ds)
        if quick:
            X, y = X[:8192], y[:8192]
        Xp, yp, mk = partition(X, y, K, seed=0)
        for lam in lams:
            for H in Hs:
                for name, cfg in [
                        ("cocoa+", CoCoAConfig.adding(K, loss="hinge",
                                                      lam=lam, H=H)),
                        ("cocoa", CoCoAConfig.averaging(K, loss="hinge",
                                                        lam=lam, H=H))]:
                    with Timer() as t:
                        r = solve(cfg, Xp, yp, mk, rounds=rounds, gap_every=5)
                    for rd, gap, comm in zip(r.history["round"],
                                             r.history["gap"],
                                             r.history["comm_vectors"]):
                        out.append(dict(dataset=ds, K=K, lam=lam, H=H,
                                        method=name, round=rd, gap=gap,
                                        comm_vectors=comm))
                    print(f"fig1,{ds},lam={lam:g},H={H},{name},"
                          f"final_gap={r.history['gap'][-1]:.3e},"
                          f"wall_s={t.s:.1f}")
    save("fig1_convergence", out)

    def draw(plt):
        for i, lam in enumerate(lams):
            ax = plt.subplot(1, len(lams), i + 1)
            for H in Hs:
                for m, c in [("cocoa+", "C0"), ("cocoa", "C3")]:
                    pts = [(r["comm_vectors"], r["gap"]) for r in out
                           if r["lam"] == lam and r["H"] == H
                           and r["method"] == m
                           and r["dataset"] == datasets[0][0]]
                    if pts:
                        xs, ys = zip(*pts)
                        ax.loglog(xs, ys, c, alpha=0.4 + 0.2 * Hs.index(H),
                                  label=f"{m} H={H}")
            ax.set_title(f"lambda={lam:g}")
            ax.set_xlabel("communicated vectors")
            if i == 0:
                ax.set_ylabel("duality gap")
                ax.legend(fontsize=6)
    maybe_plot("fig1_convergence", draw)

    # validation assertion from the paper: adding beats averaging
    for key in {(r["dataset"], r["lam"], r["H"]) for r in out}:
        finals = {m: min(r["gap"] for r in out
                         if (r["dataset"], r["lam"], r["H"]) == key
                         and r["method"] == m) for m in ("cocoa+", "cocoa")}
        status = "OK" if finals["cocoa+"] <= finals["cocoa"] * 1.15 else "VIOLATION"
        print(f"fig1-claim,{key},add={finals['cocoa+']:.3e},"
              f"avg={finals['cocoa']:.3e},{status}")
    return out


def main():
    enable_compile_cache()
    run(quick=True)


if __name__ == "__main__":
    main()
