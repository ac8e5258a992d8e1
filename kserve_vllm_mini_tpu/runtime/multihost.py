"""Multi-host serving: one HTTP URL over a process-spanning mesh.

The 70B-on-v5p-16 serving story (BASELINE.md configs[4]) needs the model
sharded across HOSTS, not just chips: 4 hosts x 4 chips join one
``jax.distributed`` runtime (parallel/distributed.py), the engine's params
and KV cache shard over the global mesh, and every jitted step is a
collective program all processes must execute in lockstep. The reference
only passes TP knobs through to engine images
(/root/reference/runners/backends/vllm/deploy.sh:78-79); here the runtime
is in-repo, so the multi-host split is explicit:

- **Process 0 (primary)** owns the HTTP frontend and the scheduler: it
  decides, per loop iteration, whether to admit a request or run a decode
  sweep — and PUBLISHES each decision (with the request payload) to the
  other processes over a host-level TCP channel before executing it.
- **Followers** replay the identical decision stream against their own
  ``Engine`` instance. Engine state evolves deterministically from the
  decision stream (same seed -> same rng splits, same slot bookkeeping,
  same readback values — outputs are replicated when dp == 1), so every
  process issues the SAME jitted calls in the SAME order with the SAME
  operands, which is exactly the contract XLA's multi-controller model
  requires. The channel carries only small host-side payloads (prompt ids,
  sampling params); tensors never cross it.

V1 scope (checked, not silent): dp == 1 meshes (tp/pp sharding — the
natural multi-host serving layouts; dp>1 would make per-slot outputs
non-addressable per process), no grammar constraints (their masks are
host-built per step; payload plumbing is straightforward but not wired),
no speculative drafter. Logprobs and sampling work — both are
deterministic device-side computations.

Lockstep hazard note: if the primary dies mid-publish, followers block in
a collective or on the channel; deploy with the pod-level failure domain
(one InferenceService replica = one process group), which is how the
reference's engines handle it too.
"""

from __future__ import annotations

import hmac
import json
import os
import socket
import struct
import threading
import time
from typing import Any, Iterator, Optional

from kserve_vllm_mini_tpu.runtime.engine import Engine, GenRequest, RequestHandle

_LEN = struct.Struct("!I")


def _channel_timeout_s() -> float:
    """Handshake window: every process must finish build_engine (minutes
    for a sharded 70B weight load) before the channel forms."""
    return float(os.environ.get("KVMINI_COMMAND_TIMEOUT", "600"))


def _channel_token() -> str:
    """Shared channel secret (KVMINI_COMMAND_TOKEN). The empty default
    still rejects stray scanners via the handshake structure; production
    deployments set a real token — the admit stream carries user
    prompts."""
    return os.environ.get("KVMINI_COMMAND_TOKEN", "")


def engine_fingerprint(engine: Engine) -> dict[str, Any]:
    """Everything that must MATCH across the process group for lockstep
    replay to produce identical jitted programs and identical state."""
    import jax

    e = engine.ecfg
    return {
        "model": engine.cfg.name,
        "vocab_size": engine.cfg.vocab_size,
        "n_layers": engine.cfg.n_layers,
        "max_slots": e.max_slots,
        "max_seq_len": e.max_seq_len,
        "max_prefill_len": e.max_prefill_len,
        "min_prefill_bucket": e.min_prefill_bucket,
        "decode_chunk": e.decode_chunk,
        "decode_pipeline": e.decode_pipeline,
        "seed": e.seed,
        "kv_cache_dtype": e.kv_cache_dtype,
        "spec_tokens": e.spec_tokens,
        "pp_microbatches": e.pp_microbatches,
        "mesh": dict(engine.mesh.shape) if engine.mesh is not None else None,
        "jax": jax.__version__,
    }


def _send_msg(conn: socket.socket, obj: Any) -> None:
    # JSON, never pickle: the hello arrives from an UNAUTHENTICATED peer,
    # and unpickling attacker bytes is arbitrary code execution — a token
    # check after the fact cannot protect the deserializer itself. Every
    # payload on this channel (hello, ack, admit/sweep/stop commands) is
    # JSON-able by construction.
    data = json.dumps(obj).encode()
    conn.sendall(_LEN.pack(len(data)) + data)


def _recv_msg(conn: socket.socket, max_len: int = 1 << 24) -> Any:
    def read_exact(n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("command channel peer closed")
            buf += chunk
        return buf

    (n,) = _LEN.unpack(read_exact(_LEN.size))
    if n > max_len:
        raise ConnectionError(f"oversized channel message ({n} bytes)")
    try:
        return json.loads(read_exact(n).decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as e:
        raise ConnectionError(f"malformed channel message: {e}") from e


class CommandPublisher:
    """Primary-side channel: accepts follower connections, verifies each
    one's handshake (shared token + engine-config fingerprint), then
    publishes JSON commands, length-prefixed, to all of them."""

    def __init__(self, host: str, port: int, n_followers: int,
                 fingerprint: Optional[dict] = None,
                 accept_timeout_s: Optional[float] = None) -> None:
        timeout = accept_timeout_s or _channel_timeout_s()
        token = _channel_token()
        self._srv = socket.create_server((host, port))
        self._conns: list[socket.socket] = []
        deadline = time.time() + timeout
        while len(self._conns) < n_followers:
            self._srv.settimeout(max(deadline - time.time(), 0.1))
            conn, addr = self._srv.accept()
            mismatch_diff = None
            try:
                conn.settimeout(10.0)
                hello = _recv_msg(conn)
                peer_tok = (hello or {}).get("token") if isinstance(hello, dict) else None
                # compare as BYTES: compare_digest on str raises for
                # non-ASCII, and a random-secret token may well contain it
                if not (isinstance(peer_tok, str)
                        and hmac.compare_digest(peer_tok.encode(), token.encode())):
                    # wrong/garbage secret: explicit rejection so a typo'd
                    # deployment fails fast on the follower side, slot NOT
                    # consumed so a scanner can't starve the real follower
                    _send_msg(conn, {"ok": False, "reason": "token mismatch"})
                    conn.close()
                    continue
                peer_fp = hello.get("fingerprint") or {}
                if fingerprint is not None and peer_fp != fingerprint:
                    mismatch_diff = {
                        k: (fingerprint.get(k), peer_fp.get(k))
                        for k in set(fingerprint) | set(peer_fp)
                        if fingerprint.get(k) != peer_fp.get(k)
                    }
                    # ack + close are best-effort: the fatal raise below
                    # must fire even if the peer already went away
                    try:
                        _send_msg(conn, {"ok": False, "reason": "config mismatch",
                                         "diff": {k: list(v) for k, v in
                                                  mismatch_diff.items()}})
                    except OSError:  # kvmini: workload-ok — best-effort nack;
                        pass         # the fatal mismatch raise below still fires
                    try:
                        conn.close()
                    except OSError:  # kvmini: workload-ok — peer already gone
                        pass
                else:
                    _send_msg(conn, {"ok": True})
                    # finite SEND timeout: publish() must never block the
                    # scheduler (or shutdown) forever on a silently-dead
                    # follower — this socket only ever sends
                    conn.settimeout(30.0)
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    self._conns.append(conn)
            except Exception:  # noqa: BLE001 — kvmini: workload-ok —
                # garbage traffic must not take the primary down;
                # authenticated-path errors surface later on publish
                try:
                    conn.close()
                except OSError:  # kvmini: workload-ok — peer already gone
                    pass
                continue
            if mismatch_diff is not None:
                # an AUTHENTICATED follower with a different engine config
                # is fatal for the whole group — lockstep would diverge
                raise ValueError(
                    f"follower {addr} engine config mismatches primary: "
                    f"{mismatch_diff}"
                )
        self._lock = threading.Lock()
        self._stopped = False

    def publish(self, cmd: tuple) -> None:
        data = json.dumps(cmd).encode()
        msg = _LEN.pack(len(data)) + data
        with self._lock:
            if self._stopped and cmd[0] == "stop":
                return  # idempotent shutdown
            if cmd[0] == "stop":
                self._stopped = True
            # attempt EVERY follower before raising: on a partial failure
            # the survivors must still get the command (above all 'stop'),
            # and any failure is fatal for lockstep so it propagates after
            first_err: Optional[OSError] = None
            for c in self._conns:
                try:
                    c.sendall(msg)
                except OSError as e:
                    first_err = first_err or e
            if first_err is not None:
                raise first_err

    def close(self) -> None:
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass
        self._srv.close()


class CommandSubscriber:
    """Follower-side channel: connects (with retries — the primary may not
    be listening yet), handshakes (token + fingerprint), and yields
    commands in publish order."""

    def __init__(self, host: str, port: int,
                 fingerprint: Optional[dict] = None,
                 connect_timeout_s: Optional[float] = None) -> None:
        timeout = connect_timeout_s or _channel_timeout_s()
        deadline = time.time() + timeout
        while True:
            try:
                self._conn = socket.create_connection((host, port), timeout=5.0)
                self._conn.settimeout(30.0)
                _send_msg(self._conn, {
                    "token": _channel_token(), "fingerprint": fingerprint,
                })
                ack = _recv_msg(self._conn)
                if not (isinstance(ack, dict) and ack.get("ok")):
                    # explicit rejection (config mismatch): NOT retryable —
                    # ValueError escapes the OSError retry loop
                    raise ValueError(f"primary rejected handshake: {ack!r}")
                break
            except OSError:
                if time.time() > deadline:
                    raise
                time.sleep(0.2)
        self._conn.settimeout(None)  # commands may be minutes apart

    def commands(self) -> Iterator[tuple]:
        while True:
            msg = _recv_msg(self._conn)
            yield tuple(msg) if isinstance(msg, list) else msg

    def close(self) -> None:
        self._conn.close()


# -- request payload (host-side fields only; tensors never cross) -----------

_REQ_FIELDS = (
    "prompt_tokens", "max_new_tokens", "temperature", "top_k", "top_p",
    "presence_penalty", "frequency_penalty",
    "eos_id", "request_id", "truncated", "truncated_tokens",
    "logprobs", "top_logprobs",
)

# every sampling-relevant GenRequest field must cross to the followers, or
# lockstep decode diverges (each process builds its own sampling arrays) —
# this guard turns "someone added a field" into a loud test failure instead
# of silent divergence
_HOST_ONLY_FIELDS = {"constraint", "adapter", "trace_id", "parent_span_id",
                     # deadline shedding is lockstep-DISABLED (engine
                     # _admit_one): the wall-clock shed decision is
                     # host-local, so the field never crosses
                     "deadline_s"}
assert set(_REQ_FIELDS) | _HOST_ONLY_FIELDS == {
    f.name for f in __import__("dataclasses").fields(GenRequest)
}, "GenRequest fields changed: update _REQ_FIELDS (or _HOST_ONLY_FIELDS)"


def req_payload(req: GenRequest) -> dict[str, Any]:
    if req.constraint is not None:
        raise ValueError(
            "multi-host serving does not support grammar constraints (v1)"
        )
    if req.adapter is not None:
        # the adapter name is resolved against the PRIMARY's bank registry;
        # followers would silently serve the base model (lockstep divergence)
        raise ValueError("multi-host serving does not support LoRA (v1)")
    return {f: getattr(req, f) for f in _REQ_FIELDS}


def req_from_payload(payload: dict[str, Any]) -> GenRequest:
    return GenRequest(**payload)


def check_multihost_engine(engine: Engine) -> None:
    """Fail fast on configurations outside the lockstep contract."""
    if engine.mesh is None:
        raise ValueError("multi-host serving needs a process-spanning mesh")
    if engine._disagg is not None:
        # the prefill lane and its handoff queue are host-local state the
        # decision stream does not carry: a follower replaying ("admit",)
        # against a lane-routed primary would prefill colocated and
        # diverge its cache/rng sequence. Loud, not silent — the v2 path
        # is a PUBLISHED handoff decision (ROADMAP item 1 notes).
        raise ValueError(
            "disaggregated prefill (disagg) is not supported under "
            "multi-host lockstep serving (v1); drop --disagg or "
            "--distributed"
        )
    if engine.mesh.shape.get("dp", 1) > 1:
        raise ValueError(
            "multi-host serving requires dp == 1 (per-slot outputs must be "
            "replicated so every process reads identical values); use tp/pp"
        )
    if engine.ecfg.spec_tokens > 0:
        raise ValueError("multi-host serving does not support a drafter (v1)")
    if engine._lora is not None:
        raise ValueError(
            "multi-host serving does not support LoRA (v1): adapter routing "
            "is resolved against the primary's bank only"
        )


def run_primary(engine: Engine, publisher: CommandPublisher,
                stop_event: threading.Event) -> None:
    """Engine's own scheduling policy (_schedule_once), with every
    state-advancing decision published to the followers before it executes
    locally — one policy, two drivers, no drift."""
    check_multihost_engine(engine)
    engine._lockstep = True  # host-local-race shortcuts off (see engine)

    def publish(decision: tuple) -> None:
        # publish_drop injection point (docs/RESILIENCE.md): an armed
        # fault silently loses this decision on the wire — the follower
        # replay diverges exactly the way a dropped packet would make
        # it, which is what the chaos scenario measures. The registry
        # is internally locked; un-armed it costs one dict miss.
        if engine._faults.check("publish_drop"):
            return
        if decision[0] == "admit":
            publisher.publish(("admit", req_payload(decision[1])))
        else:
            publisher.publish(decision)

    try:
        while not stop_event.is_set():
            # the scheduler ledger's outermost phase, as in Engine._loop
            with engine._sched.phase("loop"):
                engine._schedule_once(on_decision=publish)
    except Exception as exc:  # noqa: BLE001 — propagate as request failures
        import traceback

        traceback.print_exc()
        engine._fail_all(exc)
    finally:
        publisher.publish(("stop",))


def run_follower(engine: Engine, subscriber: CommandSubscriber) -> None:
    """Replay the primary's decision stream. Blocks until ('stop',)."""
    check_multihost_engine(engine)
    engine._lockstep = True
    # the scheduler ledger on a follower: waiting for the primary is `idle`,
    # replaying a command is `loop` and the phases it enters
    commands = subscriber.commands()
    while True:
        with engine._sched.phase("idle"):
            cmd = next(commands)
        with engine._sched.phase("loop"):
            op = cmd[0]
            if op == "admit":
                # bypass submit(): the primary already applied truncation; the
                # payload is the exact request its engine admitted
                engine._admit_one(RequestHandle(req_from_payload(cmd[1])))
            elif op == "sweep":
                engine._decode_sweep()
            elif op == "dispatch":
                # double-buffered steady state (docs/DECODE_PIPELINE.md): the
                # primary dispatched sweep N+1 before retiring sweep N. The
                # active set is deterministic from the replayed stream, so the
                # follower issues the identical jitted call with identical
                # operands (the token feed is the previous sweep's on-device
                # carry on both sides).
                engine._replay_dispatch()
            elif op == "retire":
                engine._retire_one()
            elif op == "cancel":
                # mirror the primary's early finish so the follower's slot
                # free-list stays identical for the replayed admissions
                _rid, reason = cmd[1], cmd[2]
                for slot in range(engine.ecfg.max_slots):
                    h = engine._slot_req[slot]
                    if h is not None and h.request.request_id == _rid:
                        engine._finish_slot(slot, reason)
                        break
            elif op == "stop":
                return
            else:
                raise ValueError(f"unknown multihost command {op!r}")


class PrimaryHandle:
    """Lifecycle of the primary's scheduler thread + command channel.

    ``shutdown()`` is SYNCHRONOUS: it publishes the stop command itself
    (idempotent with the thread's own finally), so followers always get
    released even when interpreter exit would otherwise freeze the daemon
    thread mid-``finally``. ``is_alive()`` feeds the HTTP health gate — a
    dead scheduler must turn the frontend unhealthy, not let requests
    queue forever."""

    def __init__(self, publisher: CommandPublisher, stop: threading.Event,
                 thread: threading.Thread) -> None:
        self._publisher = publisher
        self._stop = stop
        self._thread = thread

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
        try:
            self._publisher.publish(("stop",))
        except OSError:
            pass
        self._publisher.close()


def serve_multihost(
    engine: Engine,
    *,
    primary: bool,
    coordinator_host: str,
    command_port: int,
    n_followers: int,
) -> Optional[PrimaryHandle]:
    """Start the lockstep drivers. On the primary returns a PrimaryHandle
    (call ``shutdown()`` when the HTTP app exits); on followers BLOCKS
    until the primary publishes stop, then returns None."""
    fp = engine_fingerprint(engine)
    if primary:
        publisher = CommandPublisher(
            "0.0.0.0", command_port, n_followers, fingerprint=fp
        )
        stop = threading.Event()
        t = threading.Thread(
            target=run_primary, args=(engine, publisher, stop),
            daemon=True, name="multihost-primary",
        )
        t.start()
        return PrimaryHandle(publisher, stop, t)
    sub = CommandSubscriber(coordinator_host, command_port, fingerprint=fp)
    run_follower(engine, sub)
    return None
