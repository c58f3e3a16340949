"""Host side of the fleet: one ``FilterServer`` behind a message loop.

A :class:`HostAgent` owns a live
:class:`~repro.serve_filter.server.FilterServer` and exposes the small
op vocabulary the router drives — admit-from-wire, query, drain,
states, stats, ping, shutdown. Every op returns a dict reply with an
``ok`` flag; host-side exceptions are *serialized into the reply*
(``ok=False`` + error text/kind), never allowed to tear down the
message loop — a bad request must not look like a dead host.

Queries answer with the tenant's lifecycle state riding along
(``degraded=True`` when the tenant is serving from its backup-Bloom
fallback), so the router can map a DEGRADED replica to failover
without a second round trip.

Run standalone as a subprocess host::

    python -m repro.serve_filter.fleet --port 0 [--config '<json>']

The process binds a ``multiprocessing.connection.Listener`` on
localhost, prints ``FLEET_HOST_LISTENING <port>`` on stdout (the
parent's ready/port-discovery signal — see :func:`launch_host`) and
serves one connection at a time until a ``shutdown`` op or EOF from a
router that has moved on.
"""
from __future__ import annotations

import argparse
import os
import select
import subprocess
import sys
from multiprocessing import connection
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.runtime.compile_cache import enable_compile_cache
from repro.serve_filter.config import ServeConfig, TenantState
from repro.serve_filter.faults import FilterServeError
from repro.serve_filter.fleet import wire
from repro.serve_filter.fleet.transport import DEFAULT_AUTHKEY
from repro.serve_filter.server import FilterServer

__all__ = ["HostAgent", "run_host", "launch_host", "chip_env",
           "READY_PREFIX"]

READY_PREFIX = "FLEET_HOST_LISTENING"


class HostAgent:
    """Message-dispatch facade over one ``FilterServer``."""

    def __init__(self, server: FilterServer, *, name: str = "host"):
        self.server = server
        self.name = name
        self.shutdown_requested = False

    # ------------------------------------------------------------- ops
    def _op_ping(self, msg) -> Dict[str, Any]:
        return {"ok": True, "host": self.name}

    def _op_admit(self, msg) -> Dict[str, Any]:
        handle = self.server.admit_wire(msg["spec"])
        return {"ok": True, "tenant": handle.tenant,
                "state": handle.state.value}

    def _op_query(self, msg) -> Dict[str, Any]:
        tenant = msg["tenant"]
        ids = np.asarray(msg["ids"])
        answers = self.server.submit(tenant, ids).result()
        state = self.server.registry.state_of(tenant)
        return {"ok": True, "tenant": tenant,
                "answers": np.array(answers),
                "state": state.value,
                "degraded": state is TenantState.DEGRADED}

    def _op_state(self, msg) -> Dict[str, Any]:
        state = self.server.registry.state_of(msg["tenant"])
        return {"ok": True, "state": state.value}

    def _op_states(self, msg) -> Dict[str, Any]:
        states = self.server.registry.states()
        return {"ok": True,
                "states": {t: s.value for t, s in states.items()}}

    def _op_drain(self, msg) -> Dict[str, Any]:
        self.server.drain(msg["tenant"])
        return {"ok": True, "tenant": msg["tenant"]}

    def _op_stats(self, msg) -> Dict[str, Any]:
        return {"ok": True, "stats": self.server.stats_snapshot()}

    def _op_save(self, msg) -> Dict[str, Any]:
        path = self.server.save(msg["tenant"], msg["directory"],
                                step=int(msg.get("step", 0)))
        return {"ok": True, "path": path}

    def _op_shutdown(self, msg) -> Dict[str, Any]:
        self.shutdown_requested = True
        return {"ok": True, "host": self.name}

    # -------------------------------------------------------- dispatch
    def handle(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Dispatch one message; never raises (errors ride the reply)."""
        if not isinstance(msg, dict) or "op" not in msg:
            return {"ok": False, "error": "message must be a dict with "
                                          "an 'op' key",
                    "error_kind": "bad_request"}
        op = msg["op"]
        handler = getattr(self, f"_op_{op}", None)
        if handler is None:
            return {"ok": False, "error": f"unknown op {op!r}",
                    "error_kind": "bad_request"}
        try:
            return handler(msg)
        except FilterServeError as e:
            return {"ok": False, "error": str(e),
                    "error_kind": type(e).__name__}
        except Exception as e:   # noqa: BLE001 - the loop must survive
            return {"ok": False, "error": f"{type(e).__name__}: {e}",
                    "error_kind": type(e).__name__}


def run_host(port: int = 0, *, config: Optional[ServeConfig] = None,
             name: str = "host", authkey: bytes = DEFAULT_AUTHKEY,
             announce=print) -> None:
    """Serve a ``HostAgent`` on a localhost listener until shutdown.

    ``announce`` receives the ``FLEET_HOST_LISTENING <port>`` ready
    line once the listener is bound (stdout by default — the parent
    reads it to learn the ephemeral port)."""
    agent = HostAgent(FilterServer(config or ServeConfig()), name=name)
    with connection.Listener(("127.0.0.1", port),
                             authkey=authkey) as listener:
        announce(f"{READY_PREFIX} {listener.address[1]}", flush=True)
        while not agent.shutdown_requested:
            try:
                conn = listener.accept()
            except (connection.AuthenticationError, OSError):
                continue
            with conn:
                while not agent.shutdown_requested:
                    try:
                        msg = conn.recv()
                    except (EOFError, OSError):
                        break       # router went away; await the next
                    conn.send(agent.handle(msg))
    agent.server.close()


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that makes a process see exactly TPU chip ``chip`` of
    this host, as a one-chip slice of its own (libtpu's per-process chip
    visibility). Each such process needs its own ``TPU_PROCESS_PORT``.
    On a four-chip v5e host, processes pinned to different chips start
    and run side by side without ``ALLOW_MULTIPLE_LIBTPU_LOAD``."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(8476 + chip)}


def launch_host(*, config: Optional[ServeConfig] = None,
                name: str = "host",
                authkey: bytes = DEFAULT_AUTHKEY,
                timeout_s: float = 60.0,
                chip: Optional[int] = None
                ) -> Tuple[subprocess.Popen, Tuple[str, int]]:
    """Spawn a subprocess host and wait up to ``timeout_s`` for its ready
    line (``TimeoutError`` after killing it, if none came).

    Returns ``(proc, address)``; the caller owns the process (pair it
    with a ``shutdown`` op or ``proc.kill()``). The child gets this
    interpreter and a ``PYTHONPATH`` that can resolve ``repro``. With
    ``chip`` it sees only that TPU chip (:func:`chip_env`): a chip
    belongs to one process, so hosts sharing a machine each need one."""
    import repro
    # repro may be a namespace package (__file__ is None): resolve the
    # src dir from its search path instead
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    if chip is not None:
        env.update(chip_env(chip))
    cmd = [sys.executable, "-m", "repro.serve_filter.fleet",
           "--port", "0", "--name", name]
    if config is not None:
        cmd += ["--config", wire.dumps(wire.config_to_wire(config))]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            text=True)
    assert proc.stdout is not None
    ready, _, _ = select.select([proc.stdout], [], [], timeout_s)
    if not ready:
        proc.kill()
        proc.wait()
        raise TimeoutError(f"host {name!r} printed no ready line within "
                           f"{timeout_s} s")
    line = proc.stdout.readline()
    if not line.startswith(READY_PREFIX):
        proc.kill()
        proc.wait()
        raise RuntimeError(f"host {name!r} failed to start "
                           f"(got {line!r})")
    port = int(line.split()[1])
    return proc, ("127.0.0.1", port)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="Run one fleet serving host (router-driven).")
    parser.add_argument("--port", type=int, default=0,
                        help="listen port (0 = ephemeral, announced "
                             "on stdout)")
    parser.add_argument("--name", default="host")
    parser.add_argument("--config", default=None,
                        help="wire-form ServeConfig JSON "
                             "(default: ServeConfig())")
    args = parser.parse_args(argv)
    enable_compile_cache()
    config = None
    if args.config:
        config = wire.config_from_wire(wire.loads(args.config))
    run_host(args.port, config=config, name=args.name)


if __name__ == "__main__":
    main()
