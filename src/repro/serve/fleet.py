"""Supervised worker fleet behind one listener.

``python -m repro.serve --workers N`` boots a :class:`FleetSupervisor`
instead of a single daemon: N worker processes (each a plain
``python -m repro.serve --port 0`` on an ephemeral port) share the one
file-locked (H, C, R) store, and the supervisor's front listener proxies
every request to a worker chosen by :func:`route_index` — a stable hash
of the request's *cache class*, the observable projection of the
locality scheduler's chain key (:meth:`JobSpec.cache_group` is
``(regions, system, estimator)``; at the HTTP layer the regions are not
known yet, so the fleet routes on ``(workload, system, estimator
kind)``).  Same class -> same worker -> that worker's in-memory plan
store and coalescing window stay warm, and two workers never race the
same cold keyset.

Failure handling, in order of escalation:

* **crashed worker** — a monitor thread (and any request that trips
  over the corpse) respawns it with exponential backoff and a bumped
  *generation* (``REPRO_FAULT_GENERATION``: restarted workers do not
  replay generation-0 fault plans).
* **hung worker** — every proxied request carries the client's
  ``X-Repro-Timeout-S`` budget as its socket timeout; a worker that
  blows the budget is killed outright and the request re-dispatched to
  the next worker (predictions are pure functions of the request
  against a shared store, so re-execution is safe and mostly warm).
* **mid-stream campaign death** — the supervisor buffers every row it
  has already forwarded; on a broken stream it re-POSTs the campaign to
  another worker with those rows as ``resume_rows``, so the client's
  stream continues where it left off and at most the unflushed rows are
  recomputed.
* **circuit breaker** — after ``breaker_threshold`` *consecutive*
  worker deaths on one request class, the class is degraded for
  ``breaker_cooldown_s``: ``/predict`` answers locally from the warm
  store via the analytical (``roofline``) estimator with
  ``degraded: true`` instead of 5xx-ing or killing more workers.

Workers run with ``JAX_PLATFORMS=cpu``: exports compile for the CPU on
every host, and a chip belongs to one process.  A request that names an
estimator holding the device (``profiling``) is refused with a 400; a
single daemon serves it.

``/stats`` aggregates per-worker stats plus fleet counters (restarts,
deaths, redispatches, degraded answers, breaker state) that
``tools/bench_check.py`` pins in CI.  See ``docs/robustness.md``.
"""
from __future__ import annotations

import hashlib
import json
import os
import select
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlsplit

from .client import TIMEOUT_HEADER

__all__ = ["FleetSupervisor", "WorkerHandle", "route_index",
           "request_class", "device_holding_kinds"]


# ------------------------------ routing ------------------------------

def route_index(class_key, n: int) -> int:
    """Worker index for a request class — pure and stable across
    processes (``tools/chaos_smoke.py`` imports this to aim its fault
    plan at the worker that will actually serve the campaign)."""
    blob = json.dumps(class_key, sort_keys=True, default=str).encode()
    h = hashlib.sha256(blob).digest()
    return int.from_bytes(h[:8], "big") % max(1, n)


def request_class(path: str, body: dict) -> tuple:
    """The cache class a request belongs to: requests in one class share
    warm state, so they route to one worker and trip one breaker."""
    if path == "/predict":
        w = body.get("workload")
        name = w.get("name") if isinstance(w, dict) else w
        e = body.get("estimator", "roofline")
        kind = e.get("kind") if isinstance(e, dict) else e
        return ("predict", str(name), str(body.get("system", "a100")),
                str(kind))
    if path in ("/campaign", "/report", "/search"):
        spec = body.get("spec")
        name = (spec.get("name") if isinstance(spec, dict)
                else body.get("spec_path"))
        return (path.lstrip("/"), str(name))
    return (path.lstrip("/"),)


def device_holding_kinds(body: dict) -> list[str]:
    """The estimator kinds a request names that run on the host's device
    (``holds_device``): a ``/predict`` estimator, an inline or server-side
    campaign spec's ``estimators``, a search spec's ``ladder``."""
    from ..core.registry import ESTIMATORS

    spec = body.get("spec")
    if spec is None and body.get("spec_path"):
        try:
            with open(str(body["spec_path"])) as f:
                spec = json.load(f)
        except (OSError, ValueError):
            spec = None          # the worker reports the unreadable spec
    named = [body.get("estimator", "roofline")]
    if isinstance(spec, dict):
        named += list(spec.get("estimators", [])) + list(spec.get("ladder",
                                                                  []))
    kinds = {e.get("kind") if isinstance(e, dict) else e for e in named}
    return sorted(k for k in kinds if isinstance(k, str) and k in ESTIMATORS
                  and getattr(ESTIMATORS.get(k), "holds_device", False))


# ------------------------------ workers ------------------------------

class WorkerHandle:
    """One live worker process: its subprocess, scraped URL, and
    fault-plan generation."""

    def __init__(self, idx: int, generation: int,
                 proc: subprocess.Popen, url: str):
        self.idx = idx
        self.generation = generation
        self.proc = proc
        self.url = url
        self.started_at = time.monotonic()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


class _Breaker:
    """Per-request-class circuit breaker: ``threshold`` consecutive
    worker deaths open it for ``cooldown_s``; any success closes it."""

    def __init__(self, threshold: int, cooldown_s: float):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._lock = threading.Lock()
        self._consec: dict[tuple, int] = {}
        self._open_until: dict[tuple, float] = {}

    def record_death(self, cls: tuple) -> bool:
        """Count a death against ``cls``; True if the breaker opened."""
        with self._lock:
            n = self._consec.get(cls, 0) + 1
            self._consec[cls] = n
            if n >= self.threshold:
                self._open_until[cls] = time.monotonic() + self.cooldown_s
                return True
            return False

    def record_success(self, cls: tuple) -> None:
        with self._lock:
            self._consec.pop(cls, None)
            self._open_until.pop(cls, None)

    def is_open(self, cls: tuple) -> bool:
        with self._lock:
            until = self._open_until.get(cls)
            if until is None:
                return False
            if time.monotonic() >= until:    # cooldown over: close, reset
                del self._open_until[cls]
                self._consec.pop(cls, None)
                return False
            return True

    def open_classes(self) -> list[list]:
        with self._lock:
            now = time.monotonic()
            return [list(c) for c, t in self._open_until.items() if t > now]


class FleetSupervisor:
    """N supervised ``repro.serve`` workers behind one proxy listener.

    The supervisor owns no session of its own until a breaker opens —
    the degraded path lazily builds one local
    :class:`~repro.serve.server.PredictionService` over the same cache
    path, so degraded answers still read and extend the shared warm
    store.
    """

    def __init__(self, *, workers: int = 2, cache_path: str | None = None,
                 systems: tuple | list = (), preload: tuple | list = (),
                 host: str = "127.0.0.1", port: int = 0,
                 fault_plan: str | None = None,
                 default_timeout_s: float = 120.0,
                 backoff_s: float = 0.25, backoff_max_s: float = 5.0,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 boot_timeout_s: float = 60.0,
                 redispatch_limit: int = 2, verbose: bool = False):
        if workers < 1:
            raise ValueError("a fleet needs at least 1 worker")
        self.n = workers
        self.cache_path = cache_path
        self.systems = tuple(systems)
        self.preload = tuple(preload)
        self.fault_plan = fault_plan
        self.default_timeout_s = default_timeout_s
        self.backoff_s = backoff_s
        self.backoff_max_s = backoff_max_s
        self.boot_timeout_s = boot_timeout_s
        self.redispatch_limit = redispatch_limit
        self.verbose = verbose
        self.draining = False

        self._workers: list[WorkerHandle | None] = [None] * workers
        self._slot_locks = [threading.Lock() for _ in range(workers)]
        self._consec_deaths = [0] * workers
        self._breaker = _Breaker(breaker_threshold, breaker_cooldown_s)
        self._lock = threading.Lock()
        self._counters = {"restarts": 0, "worker_deaths": 0,
                          "redispatches": 0, "degraded": 0,
                          "hung_kills": 0, "reloads": 0}
        self._local_service = None    # lazy: only built when degrading
        self._monitor: threading.Thread | None = None
        self._thread: threading.Thread | None = None
        self.stopped = threading.Event()
        self.httpd = ThreadingHTTPServer((host, port), _make_handler(self))
        self.httpd.daemon_threads = True

    # ----------------------------- lifecycle -----------------------------

    @property
    def url(self) -> str:
        host, port = self.httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "FleetSupervisor":
        """Boot every worker, then serve the front listener on a
        background thread (tests); raises if any worker fails to boot."""
        for idx in range(self.n):
            self._workers[idx] = self._spawn(idx, generation=0)
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="repro-fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, kwargs={"poll_interval": 0.05},
            name="repro-fleet", daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        """CLI mode: boot workers and serve on the calling thread."""
        for idx in range(self.n):
            self._workers[idx] = self._spawn(idx, generation=0)
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="repro-fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        try:
            self.httpd.serve_forever(poll_interval=0.2)
        finally:
            self.stopped.set()

    def install_signal_handlers(self) -> None:
        import signal

        def _drain(signum, frame):  # noqa: ARG001
            threading.Thread(target=self.drain, daemon=True,
                             name="repro-fleet-drain").start()
        signal.signal(signal.SIGTERM, _drain)
        signal.signal(signal.SIGINT, _drain)

    def drain(self, timeout_s: float = 30.0) -> None:
        """Stop accepting work, drain every worker, stop the listener."""
        self.draining = True
        deadline = time.monotonic() + timeout_s
        for w in self._workers:
            if w is None or not w.alive():
                continue
            try:
                req = urllib.request.Request(w.url + "/shutdown", data=b"{}",
                                             method="POST")
                urllib.request.urlopen(req, timeout=5.0).read()
            except OSError:
                pass
        for w in self._workers:
            if w is None:
                continue
            try:
                w.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                w.kill()
        self.httpd.shutdown()
        self.httpd.server_close()
        self.stopped.set()

    # --------------------------- worker spawning ---------------------------

    def _spawn(self, idx: int, generation: int) -> WorkerHandle:
        cmd = [sys.executable, "-m", "repro.serve", "--port", "0"]
        if self.cache_path:
            cmd += ["--cache", self.cache_path]
        for s in self.systems:
            cmd += ["--systems", s]
        for p in self.preload:
            cmd += ["--preload", p]
        env = dict(os.environ)
        # exports compile for the CPU on every host; a worker that also
        # loaded the accelerator's runtime would fight its siblings for
        # the one chip (device-holding estimators are refused up front)
        env["JAX_PLATFORMS"] = "cpu"
        env["REPRO_FAULT_WORKER"] = str(idx)
        env["REPRO_FAULT_GENERATION"] = str(generation)
        if self.fault_plan:
            env["REPRO_FAULT_PLAN"] = self.fault_plan
        else:
            env.pop("REPRO_FAULT_PLAN", None)
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=None if self.verbose else subprocess.DEVNULL)
        try:
            url = self._scrape_url(proc)
        except Exception:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
            raise
        if self.verbose:
            print(f"fleet: worker {idx} gen {generation} up at {url} "
                  f"(pid {proc.pid})", file=sys.stderr)
        return WorkerHandle(idx, generation, proc, url)

    def _scrape_url(self, proc: subprocess.Popen) -> str:
        """First stdout line is machine-readable: ``{"url": ..., "pid":
        ...}`` — read it with a deadline so a worker that dies at import
        time fails the boot instead of hanging it."""
        deadline = time.monotonic() + self.boot_timeout_s
        fd = proc.stdout.fileno()
        buf = b""
        while b"\n" not in buf:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker exited with status {proc.returncode} "
                    "before printing its URL")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"worker did not print its URL within "
                    f"{self.boot_timeout_s:g}s")
            ready, _, _ = select.select([fd], [], [], min(remaining, 0.2))
            if ready:
                chunk = os.read(fd, 4096)
                if not chunk:
                    raise RuntimeError("worker closed stdout before "
                                       "printing its URL")
                buf += chunk
        line = buf.split(b"\n", 1)[0]
        return json.loads(line)["url"]

    def _ensure(self, idx: int) -> WorkerHandle:
        """The live handle for slot ``idx``, restarting a corpse."""
        w = self._workers[idx]
        if w is not None and w.alive():
            return w
        return self._restart(idx, w, reason="found dead")

    def _restart(self, idx: int, dead: WorkerHandle | None,
                 reason: str) -> WorkerHandle:
        """Replace slot ``idx``'s worker (exponential backoff, bumped
        generation).  Idempotent: if another thread already replaced
        ``dead``, the replacement is returned untouched."""
        with self._slot_locks[idx]:
            cur = self._workers[idx]
            if cur is not None and cur is not dead and cur.alive():
                return cur
            gen = (cur.generation if cur is not None else 0) + 1
            if cur is not None:
                cur.kill()
            with self._lock:
                self._counters["worker_deaths"] += 1
                self._consec_deaths[idx] += 1
                n_deaths = self._consec_deaths[idx]
            backoff = min(self.backoff_s * (2 ** (n_deaths - 1)),
                          self.backoff_max_s)
            if self.verbose:
                print(f"fleet: restarting worker {idx} ({reason}), "
                      f"gen {gen}, backoff {backoff:.2f}s",
                      file=sys.stderr)
            time.sleep(backoff)
            new = self._spawn(idx, generation=gen)
            self._workers[idx] = new
            with self._lock:
                self._counters["restarts"] += 1
            return new

    def _monitor_loop(self) -> None:
        """Respawn crashed workers even when no request trips over them."""
        while not self.stopped.is_set():
            if not self.draining:
                for idx in range(self.n):
                    w = self._workers[idx]
                    if w is not None and not w.alive():
                        try:
                            self._restart(idx, w, reason="monitor")
                        except Exception:  # noqa: BLE001 — keep watching
                            pass
            self.stopped.wait(0.2)

    def _mark_success(self, idx: int, cls: tuple) -> None:
        with self._lock:
            self._consec_deaths[idx] = 0
        self._breaker.record_success(cls)

    # ----------------------------- degraded -----------------------------

    def _degraded_service(self):
        """Lazy local service over the same store (breaker-open path)."""
        with self._lock:
            if self._local_service is None:
                from .server import PredictionService
                svc = PredictionService(cache_path=self.cache_path,
                                        systems=self.systems)
                for spec in self.preload:
                    svc.preload(spec)
                self._local_service = svc
            return self._local_service

    def degraded_predict(self, body: dict, reason: str) -> dict:
        """Answer a ``/predict`` locally with the analytical estimator.

        The roofline model is closed-form — it cannot hang or crash the
        way a worker just did — and it reads/writes the shared warm
        store, so repeated degraded answers for one class cost one cold
        evaluation.  The row is tagged ``degraded: true`` (plus the
        originally requested estimator when it was substituted) so no
        caller can mistake it for the real thing."""
        svc = self._degraded_service()
        body = dict(body)
        e = body.get("estimator", "roofline")
        kind = e.get("kind") if isinstance(e, dict) else e
        if kind != "roofline":
            body["estimator"] = "roofline"
        row = svc.predict(body)
        row["degraded"] = True
        row["degraded_reason"] = reason
        if kind != "roofline":
            row["requested_estimator"] = str(kind)
        with self._lock:
            self._counters["degraded"] += 1
        return row

    # ------------------------------ admin ------------------------------

    def reload_workers(self) -> dict:
        """Fan ``POST /reload`` out to every live worker — each replays
        its boot-time preloads against the specs' current on-disk
        contents.  In-flight requests are untouched (reload is just one
        more concurrent request per worker; the per-worker plan store
        only grows or swaps whole entries).  The local degraded-mode
        fallback service, when it has been instantiated, replays its
        preloads too — otherwise a breaker-open fleet would keep serving
        the stale specs while reporting a successful reload."""
        reports = []
        for idx in range(self.n):
            w = self._workers[idx]
            if w is None or not w.alive():
                reports.append({"worker": idx, "alive": False})
                continue
            try:
                req = urllib.request.Request(
                    w.url + "/reload", data=b"{}", method="POST",
                    headers={"Content-Type": "application/json"})
                rep = json.loads(
                    urllib.request.urlopen(req, timeout=30.0).read())
            except (OSError, ValueError) as e:
                reports.append({"worker": idx, "alive": w.alive(),
                                "error": f"{type(e).__name__}: {e}"})
                continue
            rep["worker"] = idx
            reports.append(rep)
        with self._lock:
            svc = self._local_service
        if svc is not None:
            try:
                rep = svc.reload()
            except Exception as e:  # noqa: BLE001 — report, don't crash
                reports.append({"worker": "local-fallback",
                                "error": f"{type(e).__name__}: {e}"})
            else:
                rep["worker"] = "local-fallback"
                reports.append(rep)
        with self._lock:
            self._counters["reloads"] += 1
        return {"reloaded": sum(1 for r in reports if "plans_built" in r),
                "workers": reports}

    # ------------------------------ stats ------------------------------

    def stats(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
        workers = []
        totals = {"predict_served": 0, "campaign_served": 0,
                  "campaign_rows": 0, "search_served": 0,
                  "duplicate_cold_misses": 0,
                  "resumed_rows": 0, "retried_rows": 0}
        for idx in range(self.n):
            w = self._workers[idx]
            if w is None or not w.alive():
                workers.append({"worker": idx, "alive": False})
                continue
            try:
                raw = urllib.request.urlopen(w.url + "/stats",
                                             timeout=10.0).read()
                st = json.loads(raw)
            except (OSError, ValueError) as e:
                workers.append({"worker": idx, "alive": w.alive(),
                                "error": f"{type(e).__name__}: {e}"})
                continue
            st.update({"worker": idx, "alive": True,
                       "generation": w.generation, "pid": w.proc.pid})
            workers.append(st)
            totals["predict_served"] += st["predict"]["served"]
            totals["campaign_served"] += st["campaign"]["served"]
            totals["campaign_rows"] += st["campaign"]["rows"]
            totals["search_served"] += st.get("search", {}).get("served", 0)
            totals["duplicate_cold_misses"] += (
                st["predict"]["duplicate_cold_misses"]
                + st["campaign"]["duplicate_cold_misses"])
            totals["resumed_rows"] += st["campaign"]["resumed_rows"]
            totals["retried_rows"] += st["campaign"]["retried_rows"]
        return {
            "fleet": {
                "workers": self.n,
                "draining": self.draining,
                **counters,
                "breaker_open": self._breaker.open_classes(),
                "generations": [
                    (w.generation if w is not None else None)
                    for w in self._workers],
            },
            "workers": workers,
            "totals": totals,
        }

    def healthz(self) -> dict:
        alive = sum(1 for w in self._workers
                    if w is not None and w.alive())
        status = ("draining" if self.draining
                  else "ok" if alive == self.n
                  else "degraded" if alive else "down")
        return {"status": status, "workers": self.n, "alive": alive}


# ------------------------------ proxying ------------------------------

def _make_handler(fleet: FleetSupervisor):

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "repro-fleet/0.1"

        def log_message(self, fmt, *args):  # noqa: A003
            if fleet.verbose:
                BaseHTTPRequestHandler.log_message(self, fmt, *args)

        def _json(self, status: int, obj: dict, *,
                  close: bool = False) -> None:
            payload = (json.dumps(obj) + "\n").encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            if close:
                self.send_header("Connection", "close")
                self.close_connection = True
            self.end_headers()
            self.wfile.write(payload)

        def _body(self) -> dict:
            n = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(n) if n else b""
            if not raw:
                return {}
            obj = json.loads(raw)
            if not isinstance(obj, dict):
                raise ValueError("request body must be a JSON object")
            return obj

        def _timeout(self) -> float:
            raw = self.headers.get(TIMEOUT_HEADER)
            try:
                t = float(raw) if raw else fleet.default_timeout_s
            except ValueError:
                t = fleet.default_timeout_s
            return max(0.1, t)

        # ------------------------- dispatch -------------------------

        def do_GET(self):  # noqa: N802
            path = urlsplit(self.path).path
            if path == "/healthz":
                self._json(200, fleet.healthz())
            elif path == "/stats":
                self._json(200, fleet.stats())
            else:
                self._json(404, {"error": f"no such endpoint {path!r}"})

        def do_POST(self):  # noqa: N802
            path = urlsplit(self.path).path
            if path == "/shutdown":
                # drain only after the acknowledgement is flushed, so
                # the process exit behind it cannot tear the response
                # out from under the client
                acked = threading.Event()

                def _drain_after_ack() -> None:
                    acked.wait(timeout=5.0)
                    fleet.drain()

                threading.Thread(target=_drain_after_ack, daemon=True,
                                 name="repro-fleet-drain").start()
                try:
                    self._json(200, {"draining": True}, close=True)
                finally:
                    acked.set()
                return
            if fleet.draining:
                self._json(503, {"error": "draining: fleet is "
                                          "shutting down"}, close=True)
                return
            try:
                body = self._body()
            except (ValueError, OSError) as e:
                self._json(400, {"error": f"bad request body: {e}"})
                return
            held = device_holding_kinds(body)
            if held:
                self._json(400, {"error": (
                    f"estimator kind(s) {held} run on the host's device, "
                    f"which one process owns; this fleet runs "
                    f"{fleet.n} worker processes — serve them from a "
                    "single daemon (--workers 1)")})
                return
            try:
                if path == "/predict":
                    self._proxy_unary(path, body, degrade=True)
                elif path == "/report":
                    self._proxy_unary(path, body, degrade=False)
                elif path == "/search":
                    self._proxy_unary(path, body, degrade=False)
                elif path == "/reload":
                    self._json(200, fleet.reload_workers())
                elif path == "/campaign":
                    self._proxy_campaign(body)
                else:
                    self._json(404, {"error": f"no such endpoint {path!r}"})
            except BrokenPipeError:
                self.close_connection = True
            except Exception as e:  # noqa: BLE001 — the fleet must live
                self._json(500, {"error": f"{type(e).__name__}: {e}"})

        # ------------------------- unary proxy -------------------------

        def _forward(self, worker: WorkerHandle, path: str, body: dict,
                     timeout: float):
            """One forwarded POST; returns (status, payload_bytes)."""
            data = json.dumps(body).encode()
            req = urllib.request.Request(
                worker.url + path, data=data, method="POST",
                headers={"Content-Type": "application/json",
                         TIMEOUT_HEADER: f"{timeout:g}"})
            try:
                resp = urllib.request.urlopen(req, timeout=timeout)
                return resp.status, resp.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        def _proxy_unary(self, path: str, body: dict,
                         *, degrade: bool) -> None:
            cls = request_class(path, body)
            budget = self._timeout()
            if degrade and fleet._breaker.is_open(cls):
                self._json(200, fleet.degraded_predict(
                    body, reason="circuit open for this request class"))
                return
            home = route_index(cls, fleet.n)
            attempts = min(fleet.n, fleet.redispatch_limit + 1)
            # the client's budget covers the WHOLE request including
            # redispatches, so each worker attempt gets a slice of it —
            # a hung first worker must leave time to kill it and ask
            # the next one
            timeout = max(0.1, budget * 0.8 / attempts)
            last: str = "no workers available"
            for attempt in range(attempts):
                idx = (home + attempt) % fleet.n
                try:
                    worker = fleet._ensure(idx)
                except Exception as e:  # noqa: BLE001 — spawn failed
                    last = f"worker {idx} failed to start: {e}"
                    continue
                try:
                    status, payload = self._forward(worker, path, body,
                                                    timeout)
                except OSError as e:
                    # timeout (hung) or reset/refused (dead): either way
                    # this worker is not coming back with an answer —
                    # kill it, count the death, go to the next worker
                    last = f"worker {idx}: {type(e).__name__}: {e}"
                    hung = isinstance(e, TimeoutError)
                    worker.kill()
                    with fleet._lock:
                        if hung:
                            fleet._counters["hung_kills"] += 1
                        if attempt + 1 < attempts:
                            fleet._counters["redispatches"] += 1
                    opened = fleet._breaker.record_death(cls)
                    try:
                        fleet._restart(idx, worker, reason=last)
                    except Exception:  # noqa: BLE001 — monitor will retry
                        pass
                    if opened:
                        break
                    continue
                fleet._mark_success(idx, cls)
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)
                return
            if degrade:
                self._json(200, fleet.degraded_predict(
                    body, reason=f"workers kept dying ({last})"))
            else:
                self._json(502, {"error": f"all workers failed: {last}"})

        # ------------------------ campaign proxy ------------------------

        def _proxy_campaign(self, body: dict) -> None:
            """Stream a campaign through a worker, re-dispatching to the
            next worker with the already-forwarded rows as
            ``resume_rows`` if the stream breaks before its summary."""
            cls = request_class("/campaign", body)
            # for a stream the budget bounds the silence *gap* between
            # rows, not the whole campaign; halving it leaves slack to
            # kill a hung worker and re-dispatch before the client's
            # own gap timer (the full budget) expires
            timeout = max(0.1, self._timeout() * 0.5)
            home = route_index(cls, fleet.n)
            attempts = fleet.redispatch_limit + 1
            forwarded: list[dict] = []
            headers_sent = False
            last = "no workers available"

            def _send_headers():
                nonlocal headers_sent
                if not headers_sent:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Connection", "close")
                    self.close_connection = True
                    self.end_headers()
                    headers_sent = True

            for attempt in range(attempts):
                idx = (home + attempt) % fleet.n
                try:
                    worker = fleet._ensure(idx)
                except Exception as e:  # noqa: BLE001 — spawn failed
                    last = f"worker {idx} failed to start: {e}"
                    continue
                try_body = dict(body)
                if forwarded or try_body.get("resume_rows"):
                    try_body["resume_rows"] = (
                        list(body.get("resume_rows") or []) + forwarded)
                data = json.dumps(try_body).encode()
                req = urllib.request.Request(
                    worker.url + "/campaign", data=data, method="POST",
                    headers={"Content-Type": "application/json",
                             TIMEOUT_HEADER: f"{timeout:g}"})
                try:
                    resp = urllib.request.urlopen(req, timeout=timeout)
                except urllib.error.HTTPError as e:
                    # the worker rejected the spec: a clean 4xx/5xx,
                    # not a death — pass it through verbatim
                    payload = e.read()
                    if not headers_sent:
                        self.send_response(e.code)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(payload)))
                        self.end_headers()
                        self.wfile.write(payload)
                    return
                except OSError as e:
                    last = f"worker {idx}: {type(e).__name__}: {e}"
                    worker.kill()
                    with fleet._lock:
                        fleet._counters["redispatches"] += 1
                    opened = fleet._breaker.record_death(cls)
                    try:
                        fleet._restart(idx, worker, reason=last)
                    except Exception:  # noqa: BLE001
                        pass
                    if opened:
                        break
                    continue
                # stream rows through, buffering for redispatch
                got_final = False
                try:
                    with resp:
                        for raw in resp:
                            line = raw.strip()
                            if not line:
                                continue
                            obj = json.loads(line)
                            if obj.get("event") in ("summary", "error"):
                                _send_headers()
                                self.wfile.write(line + b"\n")
                                self.wfile.flush()
                                got_final = True
                                break
                            _send_headers()
                            self.wfile.write(line + b"\n")
                            self.wfile.flush()
                            forwarded.append(obj)
                except (OSError, ValueError) as e:
                    last = f"worker {idx} stream: {type(e).__name__}: {e}"
                if got_final:
                    fleet._mark_success(idx, cls)
                    return
                # stream broke before the summary: the worker died (or
                # hung past the budget) mid-campaign — kill, restart,
                # re-dispatch with everything already forwarded
                last = (last if "stream" in last
                        else f"worker {idx} stream ended early")
                worker.kill()
                with fleet._lock:
                    fleet._counters["redispatches"] += 1
                opened = fleet._breaker.record_death(cls)
                try:
                    fleet._restart(idx, worker, reason=last)
                except Exception:  # noqa: BLE001
                    pass
                if opened:
                    break
            # out of attempts (or breaker open): the stream protocol is
            # already NDJSON, so the failure is an in-band error event
            _send_headers()
            final = {"event": "error",
                     "error": f"campaign failed after redispatches: {last}",
                     "rows_forwarded": len(forwarded)}
            try:
                self.wfile.write((json.dumps(final) + "\n").encode())
            except OSError:
                pass

    return Handler
