"""Replica supervisor: N serving subprocesses, restarted when they die.

Each replica is one unmodified ``kvmini-tpu serve`` process on its own
port (``serve_replica_cmd``); tests substitute any command that answers
``/healthz`` (the mock server's CLI). The supervisor owns the process
table — spawn, readiness, scale up/down, deliberate kills for chaos,
and a watchdog thread that respawns replicas that died WITHOUT being
asked to (a killed replica is a fault, not a scale-down). Scale-up
cold starts (spawn -> first healthy ``/healthz``) are measured per
replica and surfaced through the router's ``/metrics`` — the number the
paper's autoscale chapter could only infer from latency cliffs.

All state is guarded by one lock: the watchdog thread, the actuator
thread (``fleet/actuator.py``) and the router's scoreboard all read and
write the table concurrently.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Optional

# replica lifecycle states. "removed" and "stopping" mark DELIBERATE
# exits: the watchdog must not resurrect a scale-down.
STARTING = "starting"
READY = "ready"
DEAD = "dead"
REMOVED = "removed"

# default byte budget one warm-from-sibling transfer may ship: enough
# for a few hundred small blocks of int8 KV, small enough that a respawn
# storm can't saturate the host loopback
WARM_BUDGET_BYTES = 16 * 1024 * 1024


def select_donor(
    owners: dict[str, int],
    candidates: list[tuple[str, str, bool]],
    exclude: str,
) -> Optional[tuple[str, str]]:
    """Pick the KV-migration donor for a cold replica: the DEEPEST-
    owning healthy sibling (``owners`` is the router's
    ``PrefixIndex.owners()`` map, rid -> deepest owned prefix chars).
    ``candidates`` are ``(rid, url, healthy)``; the target itself is
    excluded, unhealthy replicas never donate, and a replica with no
    owned prefix (depth 0 — cold itself, e.g. JUST respawned and purged
    from the index) never donates either: migrating from a cold cache
    would ship nothing and waste the respawn window. Returns
    ``(rid, url)`` or None (cold spawn)."""
    best: Optional[tuple[str, str]] = None
    best_depth = 0
    for rid, url, healthy in candidates:
        if rid == exclude or not healthy:
            continue
        depth = int(owners.get(rid, 0))
        if depth > best_depth:
            best, best_depth = (rid, url), depth
    return best


def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (bind-0 probe; the tiny window
    between close and the replica's own bind is acceptable for a local
    fleet)."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def serve_replica_cmd(
    model: str = "llama-tiny",
    extra_args: Optional[list[str]] = None,
    env_overrides: Optional[dict[str, str]] = None,
) -> Callable[[int, str], tuple[list[str], dict[str, str]]]:
    """The default replica factory: one ``kvmini-tpu serve`` per port,
    flags appended verbatim. Returns (argv, env) per replica so a caller
    can force e.g. ``JAX_PLATFORMS=cpu`` without touching the parent's
    environment. Each child inherits the parent's view of the devices: on
    a chip machine every replica would claim the same chip (docs/FLEET.md
    "Replicas on chips")."""

    def cmd(port: int, rid: str) -> tuple[list[str], dict[str, str]]:
        argv = [
            sys.executable, "-m", "kserve_vllm_mini_tpu", "serve",
            "--model", model, "--port", str(port),
        ] + list(extra_args or [])
        env = dict(os.environ)
        env.update(env_overrides or {})
        return argv, env

    return cmd


@dataclass
class Replica:
    rid: str
    port: int
    url: str
    proc: Optional[subprocess.Popen] = None
    state: str = STARTING
    spawned_at: float = 0.0
    ready_at: Optional[float] = None
    restarts: int = 0
    log_path: Optional[Path] = None

    def cold_start_s(self) -> Optional[float]:
        if self.ready_at is None:
            return None
        return self.ready_at - self.spawned_at

    def view(self) -> dict[str, Any]:
        return {
            "rid": self.rid,
            "port": self.port,
            "url": self.url,
            "state": self.state,
            "pid": self.proc.pid if self.proc else None,
            "cold_start_s": self.cold_start_s(),
            "restarts": self.restarts,
        }


class FleetSupervisor:
    """Owns the replica process table.

    ``replica_cmd(port, rid) -> (argv, env)`` builds each replica's
    command (default: ``serve_replica_cmd()``). ``ready_timeout_s``
    bounds the spawn->healthy wait; a replica that never comes up is
    reaped and the spawn raises. ``restart_dead`` arms the watchdog
    thread (unexpected deaths respawn on the same port/rid, counted in
    ``replica_restarts``)."""

    def __init__(
        self,
        replica_cmd: Optional[Callable[[int, str], tuple[list[str], dict[str, str]]]] = None,
        host: str = "127.0.0.1",
        log_dir: Optional[Path] = None,
        ready_timeout_s: float = 120.0,
        restart_dead: bool = True,
        max_replicas: int = 8,
        poll_interval_s: float = 0.25,
        warm_from_siblings: bool = False,
        router_url: Optional[str] = None,
        warm_budget_bytes: int = WARM_BUDGET_BYTES,
        owners_fn: Optional[Callable[[], dict[str, int]]] = None,
    ) -> None:
        self.replica_cmd = replica_cmd or serve_replica_cmd()
        self.host = host
        self.log_dir = Path(log_dir) if log_dir else None
        self.ready_timeout_s = ready_timeout_s
        self.restart_dead = restart_dead
        self.max_replicas = max_replicas
        self.poll_interval_s = poll_interval_s
        # cross-replica KV migration (docs/FLEET.md): when armed, every
        # respawn/scale-up warms the fresh replica from the deepest-
        # owning healthy sibling via POST /kv/export -> /kv/import.
        # ``owners_fn`` overrides the router scrape (tests / embedded
        # routers); otherwise the ranking comes from GET
        # ``router_url``/fleet -> "kv_owners". STRICTLY best-effort: any
        # failure (donor died mid-export, router down, dense replicas)
        # counts warm_failures and the replica simply starts cold — the
        # watchdog must never wedge on a warmup.
        self.warm_from_siblings = warm_from_siblings
        self.router_url = router_url.rstrip("/") if router_url else None
        self.warm_budget_bytes = int(warm_budget_bytes)
        self._owners_fn = owners_fn
        self._warmed = 0
        self._warm_failures = 0
        # one lock for the whole table: watchdog/actuator/router threads
        # all touch it (docs/FLEET.md thread contract)
        self._lock = threading.Lock()
        self._replicas: dict[str, Replica] = {}
        self._next_id = 0
        self._desired = 0
        self._restarts_total = 0
        self._scale_ups = 0
        self._scale_downs = 0
        self._cold_starts: list[float] = []
        self._stopping = False
        self._watchdog: Optional[threading.Thread] = None

    # -- readiness ---------------------------------------------------------

    def _probe_ready(self, url: str, timeout_s: float = 2.0) -> bool:
        try:
            with urllib.request.urlopen(url + "/healthz", timeout=timeout_s) as r:
                return r.status == 200
        except Exception:  # the probe's failure IS the signal
            return False   # (replica not up yet)

    def _wait_ready(self, rep: Replica) -> None:
        deadline = time.monotonic() + self.ready_timeout_s
        while time.monotonic() < deadline:
            if rep.proc is not None and rep.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {rep.rid} exited rc={rep.proc.returncode} "
                    f"before becoming healthy"
                    + (f" (log: {rep.log_path})" if rep.log_path else "")
                )
            if self._probe_ready(rep.url):
                now = time.time()
                with self._lock:
                    rep.ready_at = now
                    rep.state = READY
                    cs = rep.cold_start_s()
                    if cs is not None:
                        self._cold_starts.append(cs)
                return
            time.sleep(0.1)
        raise RuntimeError(
            f"replica {rep.rid} not healthy within {self.ready_timeout_s}s"
            + (f" (log: {rep.log_path})" if rep.log_path else "")
        )

    # -- spawn / reap ------------------------------------------------------

    def _spawn(self, rep: Replica) -> None:
        argv, env = self.replica_cmd(rep.port, rep.rid)
        if self.log_dir is not None:
            self.log_dir.mkdir(parents=True, exist_ok=True)
            rep.log_path = self.log_dir / f"{rep.rid}.log"
            log_fh = rep.log_path.open("ab")
        else:
            log_fh = open(os.devnull, "wb")
        try:
            rep.proc = subprocess.Popen(
                argv, stdout=log_fh, stderr=subprocess.STDOUT, env=env,
                start_new_session=True,
            )
        finally:
            # the child inherited the descriptor; the parent's copy must
            # not leak one fd per spawn across a long autoscaled run
            log_fh.close()
        rep.spawned_at = time.time()
        rep.ready_at = None
        rep.state = STARTING

    def add_replica(self, wait_ready: bool = True) -> Replica:
        """Spawn one replica (the scale-up step). Blocks until healthy
        unless ``wait_ready=False``; the spawn->healthy wall is the
        cold-start sample the fleet row/report surfaces."""
        with self._lock:
            if self._stopping:
                raise RuntimeError("supervisor is stopping")
            if len(self._live()) >= self.max_replicas:
                raise RuntimeError(
                    f"fleet is at max_replicas={self.max_replicas}"
                )
            rid = f"r{self._next_id}"
            self._next_id += 1
            port = free_port(self.host)
            rep = Replica(rid=rid, port=port,
                          url=f"http://{self.host}:{port}")
            self._replicas[rid] = rep
            self._desired += 1
        self._spawn(rep)
        if wait_ready:
            try:
                self._wait_ready(rep)
            except Exception:
                self._reap(rep, deliberate=True)
                with self._lock:
                    self._desired -= 1
                raise
            self._warm_replica(rep)
        return rep

    def _live(self) -> list[Replica]:
        # caller holds the lock
        return [r for r in self._replicas.values()
                if r.state in (STARTING, READY)]

    def _reap(self, rep: Replica, deliberate: bool) -> None:
        if deliberate:
            # mark BEFORE the kill: a watchdog tick landing between the
            # signal and a late state write would read the death as
            # organic and resurrect a deliberate scale-down
            with self._lock:
                rep.state = REMOVED
        proc = rep.proc
        if proc is not None and proc.poll() is None:
            try:
                # the replica runs in its own session (process group):
                # signal the group so an engine's worker threads can't
                # orphan a wedged child
                os.killpg(proc.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                proc.wait(timeout=5.0)
        with self._lock:
            rep.state = REMOVED if deliberate else DEAD

    def remove_replica(self, rid: Optional[str] = None) -> Optional[str]:
        """Graceful scale-down of one replica (the newest by default —
        LIFO keeps the warmed-longest replicas serving). The router's
        scoreboard drops it on its next sync; in-flight requests on it
        drain through the server's own stop path."""
        with self._lock:
            live = self._live()
            if not live:
                return None
            # numeric rid order, NOT lexicographic: past r9 a string sort
            # would pick 'r9' over 'r12' and evict a warmed-old replica
            rep = (self._replicas.get(rid) if rid
                   else sorted(live, key=lambda r: int(r.rid[1:]))[-1])
            if rep is None or rep.state not in (STARTING, READY):
                return None
            self._desired = max(self._desired - 1, 0)
        self._reap(rep, deliberate=True)
        return rep.rid

    def kill_replica(self, rid: str) -> bool:
        """SIGKILL one replica — the chaos injection (``replica-kill``).
        NOT deliberate: desired count is unchanged and the watchdog (if
        armed) respawns it, which is exactly the self-healing the MTTR
        row measures."""
        with self._lock:
            rep = self._replicas.get(rid)
            if rep is None or rep.proc is None or rep.state not in (
                    STARTING, READY):
                return False
        try:
            os.killpg(rep.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            return False
        rep.proc.wait(timeout=5.0)
        # state stays STARTING/READY on purpose: the watchdog is the one
        # discoverer of deaths — it marks DEAD and respawns, exactly as
        # it would for an organic crash (one code path, one MTTR)
        return True

    # -- scaling -----------------------------------------------------------

    def scale_to(self, n: int) -> int:
        """Bring the live count to ``n`` (the actuator's one verb).
        Scale-ups block until each new replica is healthy so the
        controller's next poll sees real capacity, not pending spawns."""
        n = max(0, min(n, self.max_replicas))
        while True:
            with self._lock:
                live = len(self._live())
            if live == n:
                return n
            if live < n:
                self.add_replica(wait_ready=True)
                with self._lock:
                    self._scale_ups += 1
            else:
                if self.remove_replica() is None:
                    return live
                with self._lock:
                    self._scale_downs += 1

    def start(self, n: int) -> None:
        """Initial spawn + watchdog arm."""
        self.scale_to(n)
        if self.restart_dead and self._watchdog is None:
            self._watchdog = threading.Thread(
                target=self._watch, name="fleet-watchdog", daemon=True
            )
            self._watchdog.start()

    def _watch(self) -> None:
        while True:
            with self._lock:
                if self._stopping:
                    return
                dead = [r for r in self._replicas.values()
                        if r.state in (STARTING, READY)
                        and r.proc is not None and r.proc.poll() is not None]
                for r in dead:
                    r.state = DEAD
            for r in dead:
                try:
                    self._respawn(r)
                except Exception as e:  # noqa: BLE001 — a failed respawn
                    # must not kill the watchdog; the replica stays dead
                    # and the router routes around it (the next tick
                    # retries nothing: restarts are one-shot per death)
                    with self._lock:
                        stopping = self._stopping
                    if not stopping:  # a respawn losing the race against
                        # stop() is teardown, not a failure worth noise
                        print(f"fleet: respawn of {r.rid} failed: {e}",
                              file=sys.stderr)
            time.sleep(self.poll_interval_s)

    def _respawn(self, rep: Replica) -> None:
        """Respawn an unexpectedly-dead replica on its rid/port (the
        self-healing step the replica-kill MTTR row measures)."""
        with self._lock:
            if self._stopping or rep.state != DEAD:
                return
            rep.restarts += 1
            self._restarts_total += 1
        self._spawn(rep)
        self._wait_ready(rep)
        self._warm_replica(rep)

    # -- cross-replica KV migration (docs/FLEET.md) ------------------------

    def _post_json(self, url: str, body: dict[str, Any],
                   timeout_s: float = 30.0) -> dict[str, Any]:
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return json.loads(r.read().decode())

    def _owners(self) -> dict[str, int]:
        if self._owners_fn is not None:
            return dict(self._owners_fn() or {})
        if not self.router_url:
            return {}
        with urllib.request.urlopen(
            self.router_url + "/fleet", timeout=5.0
        ) as r:
            doc = json.loads(r.read().decode())
        return dict(doc.get("kv_owners") or {})

    def _warm_replica(self, rep: Replica) -> bool:
        """Warm a freshly-(re)spawned replica's prefix cache from the
        deepest-owning healthy sibling: GET the router's donor ranking,
        POST the donor's /kv/export (bounded byte budget), POST the
        payload into the target's /kv/import. Pure HTTP, pure
        best-effort: every failure path logs, counts warm_failures, and
        returns False — a dead donor mid-export degrades to a cold spawn
        without wedging the watchdog."""
        if not self.warm_from_siblings:
            return False
        try:
            owners = self._owners()
            with self._lock:
                candidates = [
                    (r.rid, r.url, r.state == READY
                     and r.proc is not None and r.proc.poll() is None)
                    for r in self._replicas.values()
                ]
            donor = select_donor(owners, candidates, exclude=rep.rid)
            if donor is None:
                return False
            payload = self._post_json(
                donor[1] + "/kv/export",
                {"budget_bytes": self.warm_budget_bytes},
            )
            if not payload.get("blocks"):
                return False
            res = self._post_json(rep.url + "/kv/import", payload)
            with self._lock:
                self._warmed += 1
            print(
                f"fleet: warmed {rep.rid} from {donor[0]}: "
                f"{res.get('imported', 0)} blocks, "
                f"{res.get('bytes', 0)} bytes", file=sys.stderr,
            )
            return True
        except Exception as e:  # noqa: BLE001 — warmup must never wedge
            # the watchdog or fail a respawn; cold spawn is the fallback
            with self._lock:
                self._warm_failures += 1
            print(f"fleet: warm of {rep.rid} failed (cold spawn): {e}",
                  file=sys.stderr)
            return False

    # -- introspection -----------------------------------------------------

    def replicas(self) -> list[dict[str, Any]]:
        with self._lock:
            return [r.view() for r in self._replicas.values()
                    if r.state != REMOVED]

    def live_urls(self) -> list[tuple[str, str]]:
        """(rid, url) of replicas worth routing to — the router's
        scoreboard syncs from this every tick (pull model: no
        cross-thread pushes into the event loop)."""
        with self._lock:
            return [(r.rid, r.url) for r in self._replicas.values()
                    if r.state in (STARTING, READY)]

    def counters(self) -> dict[str, Any]:
        with self._lock:
            return {
                "desired": self._desired,
                "live": len(self._live()),
                "restarts": self._restarts_total,
                "scale_ups": self._scale_ups,
                "scale_downs": self._scale_downs,
                "last_cold_start_s": (
                    self._cold_starts[-1] if self._cold_starts else None
                ),
                "cold_starts_s": list(self._cold_starts),
                "warmed": self._warmed,
                "warm_failures": self._warm_failures,
            }

    def stop(self) -> None:
        with self._lock:
            self._stopping = True
            reps = list(self._replicas.values())
        for rep in reps:
            if rep.state in (STARTING, READY, DEAD):
                self._reap(rep, deliberate=True)
        if self._watchdog is not None:
            self._watchdog.join(timeout=5.0)
        # second reap pass AFTER the watchdog is gone: a _respawn that
        # passed its _stopping check just before stop() set the flag may
        # have spawned a fresh process (own session — it would outlive
        # us) after the first pass reaped only the old dead pid
        with self._lock:
            reps = list(self._replicas.values())
        for rep in reps:
            if rep.proc is not None and rep.proc.poll() is None:
                self._reap(rep, deliberate=True)

    def __enter__(self) -> "FleetSupervisor":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()


def mock_replica_cmd(
    repo_root: Optional[Path] = None,
    token_delay_s: float = 0.002,
    n_tokens: int = 8,
    metrics: Optional[dict[str, float]] = None,
) -> Callable[[int, str], tuple[list[str], dict[str, str]]]:
    """Replica factory for JAX-free fleets: one ``tests/mock_server.py``
    CLI process per port (the multi-instance satellite). Used by the
    fleet tests and the chaos smoke — a real HTTP socket per replica,
    kill-able, no engine behind it."""
    root = str(repo_root or Path(__file__).resolve().parents[2])

    def cmd(port: int, rid: str) -> tuple[list[str], dict[str, str]]:
        argv = [
            sys.executable, "-m", "tests.mock_server",
            "--port", str(port), "--server-id", rid,
            "--token-delay", str(token_delay_s),
            "--n-tokens", str(n_tokens),
        ]
        if metrics:
            argv += ["--metrics-json", json.dumps(metrics)]
        env = dict(os.environ)
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        return argv, env

    return cmd
