"""Driver of the resident control plane (`repro.fleet.service.FleetService`).

Set-up builds the service from the configuration, warms its capacity
bucket, attaches the traffic's packages (tenants in contiguous blocks,
workload kinds cycling), serves HTTP on an ephemeral localhost port and
sets the canary fraction over ``POST /canary``.

The window is a closed loop of ``tick()`` flushes, as the service's own
serving loop runs them, beside an open loop of operator reads
(``GET /telemetry?last=1``) due at a fixed rate, each on its own thread and
timed from when it was due.  The window ends at the end of the last flush
begun within the window's seconds; reads due before that count.

The check replays every flush of the window through the plain reference:
each package's density is drawn again from its own key, independently of
the service's chunk assembly, and each flush's fleet telemetry and
per-tenant statistics are compared with the service's flush record.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.harness import (SEED_SPAN, TELEMETRY_FIELDS, Phases, quantile,
                           rel_err)

KEY_SPAN = SEED_SPAN - 2 ** 20     # service keys are seed + attach index

TELEMETRY_COUNTS = ("n_packages", "events_total", "events_step",
                    "degraded_count")
TENANT_FIELDS = ("temp_peak_c", "freq_min", "freq_mean", "at_risk_frac",
                 "drift_nm")
TENANT_COUNTS = ("n_lanes", "events", "degraded_lanes")


class Driver:
    unit_span = "bench.tick"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic = config, traffic
        self.seed = int(seed) % KEY_SPAN
        self.devices = devices
        self.n = int(config["fleet_packages"])
        self.flushes: list[tuple[float, float]] = []
        self.reads: list[tuple[float, float, float, bool]] = []
        self.records: list[dict] = []
        self.attempted = self.failed = self.n_units = 0

    # ------------------------------------------------------------- set-up
    def package(self, i: int) -> tuple[str, str, str]:
        """(name, tenant, workload kind) of the i-th attached package.
        Tenants hold contiguous blocks of the fleet, and names sort in
        attach order, so a canary fraction of one tenant's share pins
        exactly the first tenant."""
        from bench.reference.workload import KINDS
        per = -(-self.n // self.traffic["tenants"])
        return (f"pkg{i:06d}", f"tenant{i // per:02d}",
                KINDS[i % len(KINDS)])

    def setup(self) -> None:
        from repro.core.scheduler import SchedulerConfig
        from repro.fleet.service import FleetService, serve_http
        c = self.cfg
        ph = self.phases = Phases()
        svc = FleetService(SchedulerConfig(**c["scheduler"]),
                           backend=c["backend"], seed=self.seed,
                           **c["service"])
        ph.mark("service")
        svc.warmup(max_packages=self.n)
        ph.mark("warmup")
        for i in range(self.n):
            name, tenant, kind = self.package(i)
            svc.attach(name, tenant=tenant, kind=kind)
        ph.mark("attach")
        self.server, _ = serve_http(svc, port=0)
        self.base = f"http://127.0.0.1:{self.server.server_address[1]}"
        self.svc = svc
        out = self._call("/canary",
                         {"reactive_frac": self.traffic["canary_frac"]})
        self.pinned = int(out["pinned_reactive"])
        self._call(self.traffic["read_path"])          # warm the read path
        ph.mark("http")

    def _call(self, path: str, body: dict | None = None,
              timeout: float = 120.0) -> dict:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            if r.status != 200:
                raise RuntimeError(f"{path} answered {r.status}")
            return json.loads(r.read())

    # ------------------------------------------------------------- window
    def _read(self, due: float) -> None:
        import jax
        sent = time.perf_counter()
        ok = True
        try:
            with jax.profiler.TraceAnnotation("bench.read"):
                self._call(self.traffic["read_path"],
                           timeout=self.traffic["read_timeout_s"])
        except Exception:               # noqa: BLE001 - a failed read
            ok = False
        self.reads.append((due, sent, time.perf_counter(), ok))

    def _client(self, t0: float, stop: threading.Event, end: list,
                pool: ThreadPoolExecutor) -> None:
        period = 1.0 / self.traffic["read_rate_per_s"]
        i = 0
        while True:
            due = t0 + i * period
            if stop.is_set() and due >= end[0]:
                return
            wait = due - time.perf_counter()
            if wait > 0:
                stop.wait(wait)          # wakes early when the window ends
                continue
            pool.submit(self._read, due)
            i += 1

    def run(self, seconds: float) -> None:
        import jax
        stop, end = threading.Event(), [float("inf")]
        pool = ThreadPoolExecutor(max_workers=self.traffic["read_workers"])
        t0 = time.perf_counter()
        client = threading.Thread(target=self._client,
                                  args=(t0, stop, end, pool), daemon=True)
        client.start()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(self.unit_span):
                rec = self.svc.tick()
            self.flushes.append((a, time.perf_counter()))
            self.records.append(rec)
        self.t0, self.t1 = t0, self.flushes[-1][1]
        end[0] = self.t1
        stop.set()
        client.join()
        pool.shutdown(wait=True)
        self.server.shutdown()
        self.server.server_close()
        self.attempted = len(self.reads) + len(self.flushes)
        self.n_units = len(self.flushes)
        self.failed = sum(1 for r in self.reads if not r[3])

    # ------------------------------------------------------------ results
    def _latencies_ms(self) -> list[float]:
        return [(done - due) * 1e3 for due, _, done, _ in self.reads]

    def end_to_end(self) -> dict:
        steps = len(self.flushes) * self.n * self.cfg["service"][
            "flush_every"]
        return {"pkg_steps_per_s": steps / (self.t1 - self.t0),
                "api_p95_ms": quantile(self._latencies_ms(), 95)}

    def notes(self) -> list[str]:
        lat = self._latencies_ms()
        late = [(sent - due) * 1e3 for due, sent, _, _ in self.reads]
        host = [(b - a) * 1e3 for a, b in self.flushes]
        return [
            self.phases.line(),
            f"[flush] {len(self.flushes)} flushes of {self.n} packages x "
            f"{self.cfg['service']['flush_every']} steps; tick() host time "
            f"median {quantile(host, 50):.1f} ms, max {max(host):.1f} ms; "
            f"{self.pinned} packages pinned reactive",
            f"[api] {len(lat)} reads at "
            f"{self.traffic['read_rate_per_s']}/s, {self.failed} failed; "
            f"latency p50 {quantile(lat, 50):.1f} ms p95 "
            f"{quantile(lat, 95):.1f} ms max {max(lat):.1f} ms",
            f"[api] client lateness (send - due) p50 "
            f"{quantile(late, 50):.2f} ms p95 {quantile(late, 95):.2f} ms "
            f"max {max(late):.2f} ms",
        ]

    def trace_context(self) -> dict:
        from bench.kernel_bytes import flush_bytes
        return {"unit_span": self.unit_span, "unit_bytes": flush_bytes(
            self.cfg["scheduler"], self.cfg["service"]["flush_every"],
            self.n)}

    def release(self) -> None:
        del self.svc

    # -------------------------------------------------------------- check
    def reference_records(self, n_flushes: int, dtype=None) -> list[dict]:
        """The plain reference's flush records for flushes 0..n-1."""
        import jax
        import jax.numpy as jnp

        from bench.reference.fleet import FleetRef, tenant_stats
        ref = FleetRef(self.cfg["scheduler"],
                       dtype=dtype or jnp.float32)
        n = self.n
        pkgs = [self.package(i) for i in range(n)]
        pinned = set(sorted(p[0] for p in pkgs)[:self.pinned_count()])
        pins = jnp.asarray([p[0] in pinned for p in pkgs])
        groups = {}
        for i, (_, tenant, _) in enumerate(pkgs):
            groups.setdefault(tenant, []).append(i)
        groups = {k: np.asarray(v) for k, v in groups.items()}
        steps = self.cfg["service"]["flush_every"]
        tiles = self.cfg["scheduler"]["n_tiles"]
        chunk_fn = _chunk_fn(steps, tiles, n)
        seeds = jnp.asarray((self.seed + np.arange(n)).astype(np.uint32))
        st = ref.init(n)
        out = []
        for f in range(n_flushes):
            chunk = chunk_fn(seeds, jnp.uint32(f))
            st, telem, lanes = ref.window(st, chunk, pins)
            telem = jax.device_get(telem)
            out.append({"telemetry": {k: float(v) for k, v in telem.items()},
                        "tenants": tenant_stats(lanes, groups, steps,
                                                tiles)})
        return out

    def pinned_count(self) -> int:
        return int(round(self.traffic["canary_frac"] * self.n))

    def control(self, n_flushes: int, dtype=None) -> dict:
        """The compared numbers when the reference computed in ``dtype``
        (bfloat16 by default) stands in the service's place."""
        import jax.numpy as jnp
        return compare(self.reference_records(n_flushes,
                                              dtype or jnp.bfloat16),
                       self.reference_records(n_flushes))

    def check(self) -> dict:
        return compare(self.records, self.reference_records(
            len(self.records)))


def _chunk_fn(n_steps: int, n_tiles: int, n: int):
    """Jitted [n_steps, n, n_tiles] density of flush ``f``: package i runs
    kind KINDS[i % 4] from key fold_in(PRNGKey(seed_i), f)."""
    import jax
    import jax.numpy as jnp

    from bench.reference.workload import KINDS, make_trace

    @jax.jit
    def chunk(seeds, f):
        out = jnp.zeros((n_steps, n, n_tiles), jnp.float32)
        for j, kind in enumerate(KINDS):
            keys = jax.vmap(lambda s: jax.random.fold_in(
                jax.random.PRNGKey(s), f))(seeds[j::len(KINDS)])
            tr = jax.vmap(lambda k: make_trace(k, n_steps, kind,
                                               n_tiles))(keys)
            out = out.at[:, j::len(KINDS), :].set(jnp.moveaxis(tr, 0, 1))
        return out
    return chunk


def compare(records: list[dict], refs: list[dict]) -> dict:
    """``telemetry_err``: worst |prog - ref| / max(|ref|, 1) over the
    continuous fleet-telemetry and per-tenant fields of every flush;
    ``count_err``: worst absolute difference of an event, degraded or
    membership count."""
    err, cnt = 0.0, 0.0
    if len(records) != len(refs) or not records:
        return {"telemetry_err": float("inf"), "count_err": float("inf")}
    for rec, ref in zip(records, refs):
        pt, rt = rec["telemetry"], ref["telemetry"]
        for k in TELEMETRY_FIELDS:
            err = max(err, rel_err(pt[k], rt[k]))
        for k in TELEMETRY_COUNTS:
            cnt = max(cnt, abs(float(pt[k]) - float(rt[k])))
        if set(rec["tenants"]) != set(ref["tenants"]):
            return {"telemetry_err": float("inf"),
                    "count_err": float("inf")}
        for name, r in ref["tenants"].items():
            p = rec["tenants"][name]
            for k in TENANT_FIELDS:
                err = max(err, rel_err(p[k], r[k]))
            for k in TENANT_COUNTS:
                cnt = max(cnt, abs(float(p[k]) - float(r[k])))
    return {"telemetry_err": err, "count_err": cnt}
