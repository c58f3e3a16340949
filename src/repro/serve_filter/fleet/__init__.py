"""Fleet federation: the routing tier above single-process servers.

Module map (one concern per module, mirroring the serving package):

* ``wire``      — versioned JSON codec for the frozen configs
  (``ServeConfig``/``TenantSpec``): bit-stable round trip, closed
  schema, ``WIRE_SCHEMA_VERSION`` envelope;
* ``ring``      — seeded consistent-hash ring (deterministic
  placement, minimal movement on host loss);
* ``transport`` — ``request(msg) -> reply`` to one host: in-process
  for tests/examples, ``multiprocessing.connection`` sockets for real
  host processes; every connection failure is ``HostUnreachable``;
* ``host``      — ``HostAgent`` (the op vocabulary a router drives
  against one ``FilterServer``), the ``python -m ...fleet.host``
  process entry point, and ``launch_host`` for spawning them;
* ``router``    — ``FilterRouter``: placement + load overrides,
  replica fan-out, failover/recovery, lifecycle-driven rebalance, and
  the pinned ``router_*`` snapshot.
"""
from repro.serve_filter.fleet.host import (HostAgent, chip_env,
                                           launch_host, run_host)
from repro.serve_filter.fleet.ring import HashRing
from repro.serve_filter.fleet.router import (ROUTER_SNAPSHOT_KEYS,
                                             FilterRouter, RouterStats)
from repro.serve_filter.fleet.transport import (DEFAULT_AUTHKEY,
                                                HostTransport,
                                                HostUnreachable,
                                                InProcessTransport,
                                                SocketTransport)
from repro.serve_filter.fleet.wire import (WIRE_SCHEMA_VERSION, WireError,
                                           config_from_wire,
                                           config_to_wire,
                                           spec_from_wire, spec_to_wire)

__all__ = [
    "FilterRouter", "RouterStats", "ROUTER_SNAPSHOT_KEYS",
    "HashRing", "HostAgent", "run_host", "launch_host", "chip_env",
    "HostTransport", "InProcessTransport", "SocketTransport",
    "HostUnreachable", "DEFAULT_AUTHKEY",
    "WIRE_SCHEMA_VERSION", "WireError",
    "config_to_wire", "config_from_wire",
    "spec_to_wire", "spec_from_wire",
]
