"""Driver of the §10 Monte Carlo (`repro.core.montecarlo.run`).

Set-up runs one experiment with a key no window uses, which compiles every
program the window's experiments run.  The window runs experiments back to
back, experiment i with key ``fold_in(PRNGKey(seed), i)``, each followed
by ``.stats()`` (its host sync), as a pre-silicon team runs them.  The
window ends at the end of the last experiment begun within its seconds.
Both fleets of the paired experiment count: 2 x trials x steps
package-steps per experiment.

The check runs the plain per-trial reference on sampled experiments (the
first, the last and one drawn from the seed) and compares the §10 summary
statistics.
"""
from __future__ import annotations

import time

from bench.harness import SEED_SPAN, rel_err, sampled
SMOOTH = ("baseline_mean_c", "baseline_std_c", "baseline_time_above_frac",
          "v24_mean_c", "v24_std_c", "v24_time_above_frac", "uplift_mean")
ORDER = ("uplift_p5", "uplift_p95")


class Driver:
    unit_span = "bench.mc_run"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic = config, traffic
        self.seed = int(seed) % SEED_SPAN
        self.devices = devices
        self.attempted = self.failed = self.n_units = 0

    def _run(self, key):
        from repro.core import montecarlo
        c = self.cfg["montecarlo"]
        return montecarlo.run(key=key, n_trials=c["n_trials"],
                              n_steps=c["n_steps"], burn_in=c["burn_in"],
                              backend=self.cfg["backend"])

    def key(self, i: int):
        import jax
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), i)

    def setup(self) -> None:
        # a key no window reaches: the window's key derivation is warmed too
        self._run(self.key(2 ** 30)).stats()

    def run(self, seconds: float) -> None:
        import jax
        self.results, self.stats = [], []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation(self.unit_span):
                r = self._run(self.key(i))
                self.stats.append(r.stats())
            self.results.append(r)
            i += 1
        self.t0, self.t1 = t0, time.perf_counter()
        self.attempted = len(self.results)
        self.n_units = len(self.results)

    def end_to_end(self) -> dict:
        c = self.cfg["montecarlo"]
        steps = 2 * c["n_trials"] * c["n_steps"] * len(self.results)
        return {"pkg_steps_per_s": steps / (self.t1 - self.t0)}

    def notes(self) -> list[str]:
        c = self.cfg["montecarlo"]
        per = (self.t1 - self.t0) / max(len(self.results), 1)
        return [f"[mc] {len(self.results)} experiments of {c['n_trials']} "
                f"trials x {c['n_steps']} steps (paired), {per * 1e3:.1f} ms "
                f"per experiment"]

    def trace_context(self) -> dict:
        from bench.kernel_bytes import experiment_bytes
        from bench.reference.montecarlo import WINDOW
        c = self.cfg["montecarlo"]
        return {"unit_span": self.unit_span, "unit_bytes": experiment_bytes(
            c["n_trials"], c["n_steps"], WINDOW)}

    def release(self) -> None:
        pass

    # -------------------------------------------------------------- check
    def reference_stats(self, i: int, dtype=None) -> dict:
        import jax.numpy as jnp

        from bench.reference import montecarlo as ref
        c = self.cfg["montecarlo"]
        r = ref.experiment(self.key(i), c["n_trials"], c["n_steps"],
                           c["burn_in"], dtype or jnp.float32)
        return ref.stats(r)

    def control(self, n_experiments: int, dtype=None) -> dict:
        """The compared numbers when the reference computed in ``dtype``
        (bfloat16 by default) stands in the program's place."""
        import jax.numpy as jnp
        sample = sampled(self.seed, n_experiments)
        return compare([self.reference_stats(i, dtype or jnp.bfloat16)
                        for i in sample],
                       [self.reference_stats(i) for i in sample])

    def check(self) -> dict:
        sample = sampled(self.seed, len(self.results))
        return compare([self.stats[i] for i in sample],
                       [self.reference_stats(i) for i in sample])


def compare(prog: list[dict], refs: list[dict]) -> dict:
    """``stats_err``: worst |prog - ref| / max(|ref|, 1) over the smooth §10
    statistics (means, deviations, exceedance, mean uplift);
    ``tail_err``: the same over the uplift's 5th and 95th percentiles."""
    if len(prog) != len(refs) or not prog:
        return {"stats_err": float("inf"), "tail_err": float("inf")}
    pairs = list(zip(prog, refs))
    return {"stats_err": max(rel_err(p[k], r[k])
                             for p, r in pairs for k in SMOOTH),
            "tail_err": max(rel_err(p[k], r[k])
                            for p, r in pairs for k in ORDER)}
