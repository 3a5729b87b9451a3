"""Paper Figure 2: the effect of increasing K on time-to-epsilon.

On this CPU-only container wall-time is not the paper's cluster wall-time,
so the primary metric is ROUNDS (== synchronous communication phases) and
communicated d-vectors to reach an epsilon-accurate duality gap; CPU wall
seconds are reported as a secondary column. Claims under test: CoCoA
degrades ~linearly in K, CoCoA+ stays flat (strong scaling); mini-batch
SGD/CD are an order of magnitude behind (paper section 7.3)."""
from __future__ import annotations

import numpy as np

from repro.core import CoCoAConfig, solve
from repro.core.baselines import run_minibatch_cd, run_minibatch_sgd
from repro.data import load, partition
from repro.launch.compile_cache import enable_compile_cache

from .common import Timer, maybe_plot, save


def rounds_to_eps(hist, eps):
    for rd, gap in zip(hist["round"], hist["gap"]):
        if gap <= eps:
            return rd
    return None


def run(quick: bool = True):
    X, y = load("epsilon_like")
    if quick:
        X, y = X[:8192], y[:8192]
    lam, eps = (1e-3, 1e-3) if quick else (1e-4, 1e-3)
    Ks = [4, 8, 16] if quick else [4, 8, 16, 32, 64]
    max_rounds = 250 if quick else 400
    out = []
    for K in Ks:
        Xp, yp, mk = partition(X, y, K, seed=0)
        H = 1024 if quick else 10_000           # fixed local work per round
        for name, cfg in [("cocoa+", CoCoAConfig.adding(K, loss="hinge",
                                                        lam=lam, H=H)),
                          ("cocoa", CoCoAConfig.averaging(K, loss="hinge",
                                                          lam=lam, H=H))]:
            with Timer() as t:
                r = solve(cfg, Xp, yp, mk, rounds=max_rounds, eps_gap=eps,
                          gap_every=2)
            rd = rounds_to_eps(r.history, eps)
            out.append(dict(K=K, method=name, rounds=rd,
                            comm_vectors=(rd or max_rounds) * K,
                            final_gap=r.history["gap"][-1], wall_s=t.s))
            print(f"fig2,K={K},{name},rounds_to_eps={rd},wall_s={t.s:.1f}")
        # mini-batch CD baseline: same per-round communication, tiny batches
        with Timer() as t:
            (_, _), hist = run_minibatch_cd(Xp, yp, mk, loss_name="hinge",
                                            lam=lam, rounds=max_rounds,
                                            b_local=16, eval_every=10)
        rd = rounds_to_eps(hist, eps)
        out.append(dict(K=K, method="minibatch-cd", rounds=rd,
                        comm_vectors=(rd or max_rounds) * K,
                        final_gap=hist["gap"][-1], wall_s=t.s))
        print(f"fig2,K={K},minibatch-cd,rounds_to_eps={rd}")
        # mini-batch SGD baseline (primal suboptimality proxy: no certificate)
        with Timer() as t:
            _, hist = run_minibatch_sgd(Xp, yp, mk, loss_name="hinge",
                                        lam=lam, steps=max_rounds,
                                        b_local=16, eval_every=25)
        out.append(dict(K=K, method="minibatch-sgd", rounds=None,
                        comm_vectors=max_rounds * K,
                        final_primal=hist["primal"][-1], wall_s=t.s))
        print(f"fig2,K={K},minibatch-sgd,final_primal={hist['primal'][-1]:.4f}")
    save("fig2_scaling", out)

    def draw(plt):
        for m, c in [("cocoa+", "C0"), ("cocoa", "C3"), ("minibatch-cd", "C2")]:
            pts = [(r["K"], r["rounds"]) for r in out
                   if r["method"] == m and r.get("rounds")]
            if pts:
                xs, ys = zip(*pts)
                plt.plot(xs, ys, f"{c}o-", label=m)
        plt.xlabel("K (machines)")
        plt.ylabel(f"rounds to gap <= 1e-3")
        plt.xscale("log", base=2)
        plt.yscale("log")
        plt.legend()
        plt.title("Strong scaling (paper Fig. 2)")
    maybe_plot("fig2_scaling", draw)

    # claim check: averaging degrades faster than adding as K grows
    radd = {r["K"]: r["rounds"] for r in out if r["method"] == "cocoa+"}
    ravg = {r["K"]: r["rounds"] for r in out if r["method"] == "cocoa"}
    ks = sorted(k for k in radd if radd[k] and ravg.get(k))
    if len(ks) >= 2:
        g_add = (radd[ks[-1]] or 1) / (radd[ks[0]] or 1)
        g_avg = (ravg[ks[-1]] or 1) / (ravg[ks[0]] or 1)
        print(f"fig2-claim,growth add={g_add:.2f}x avg={g_avg:.2f}x,"
              f"{'OK' if g_avg >= g_add else 'VIOLATION'}")
    return out


def main():
    enable_compile_cache()
    run(quick=True)


if __name__ == "__main__":
    main()
