#!/usr/bin/env python3
"""Record a small profiler trace of the served path on the chip, with the
program's ``serve.*`` spans, for a test of ``bench/lib/hostsplit.py``.

    python3 bench/record_serve_trace.py OUT.xplane.txt

On the chip: the small cell of ``bench/tests/tiny.py`` (8 tenants of
the airplane configuration cut to a few thousand records) set up as
``bench/run.py --trace 1`` sets up a cell, then four rounds inside one
``bench.window``, each a ``bench.submit`` of 16 rows for each of three
tenants (rounds 0 and 2) or for one (rounds 1 and 3) and a
``bench.step`` that steps the server until they are answered: four
grouped dispatches, two of which find their tile layout cached. Traced
with the options of a traced run (``xtrace.options``). The profiler's
file holds some 300 KB, most of it the stats of events no reduction
reads; ``OUT`` is the text ``XSpace`` of what ``xtrace`` and
``hostsplit`` read (``transcribe``), some 50 KB, which
``jax.profiler.ProfileData.from_text_proto`` loads.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def transcribe(pd) -> str:
    """The host's ``bench.*`` and ``serve.*`` events and the first TPU's
    ``XLA Ops`` and ``XLA Modules`` lines of the trace ``pd``, as a
    text ``XSpace``: each event at its own start and duration, names in
    the event metadata; no stats, no other line or plane."""
    planes = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            def keep(line):
                return [e for e in line.events
                        if e.name.startswith(("bench.", "serve."))]
        elif plane.name == "/device:TPU:0":
            def keep(line):
                return (list(line.events)
                        if line.name in ("XLA Ops", "XLA Modules") else [])
        else:
            continue
        meta, lines = {}, []
        for line in plane.lines:
            events = [f"events {{ metadata_id: "
                      f"{meta.setdefault(e.name, len(meta) + 1)} "
                      f"offset_ps: {round(e.start_ns * 1000)} "
                      f"duration_ps: {round(e.duration_ns * 1000)} }}"
                      for e in keep(line)]
            if events:
                lines.append(f"lines {{ id: {len(lines) + 1} name: "
                             f"{json.dumps(line.name)} timestamp_ns: 0\n"
                             + "\n".join(events) + "\n}")
        names = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{json.dumps(n)} }} }}" for n, i in meta.items()]
        planes.append(f"planes {{ id: {len(planes) + 1} name: "
                      f"{json.dumps(plane.name)}\n"
                      + "\n".join(lines + names) + "\n}")
    return "\n".join(planes) + "\n"


def main() -> int:
    import jax
    from jax.profiler import ProfileData
    from bench import run
    from bench.lib import xtrace
    from bench.tests import tiny
    if jax.default_backend() != "tpu":
        print("record_serve_trace: JAX found no TPU", file=sys.stderr)
        return 3
    su = run.set_up(tiny.config(), tiny.mix(), SimpleNamespace(
        seed=tiny.SEED, trace=1), {}, jax)
    server = su.server
    arena, = server.registry.groups.values()
    hits, misses = arena.tile_hits, arena.tile_misses

    def rows(t):
        return su.pools[su.rel_of(t)][16 * t:16 * t + 16]

    tmp = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(tmp, profiler_options=xtrace.options()):
            with jax.profiler.TraceAnnotation("bench.window"):
                for rnd in range(4):
                    tenants = (0, 1, 2) if rnd % 2 == 0 else (3,)
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        futs = server.submit_many(
                            [(su.names[t], rows(t)) for t in tenants])
                    with jax.profiler.TraceAnnotation("bench.step"):
                        while not all(f.done() for f in futs):
                            server.step()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        with open(sys.argv[1], "w") as f:
            f.write(transcribe(ProfileData.from_file(path)))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_serve_trace: wrote {sys.argv[1]} "
          f"({os.path.getsize(sys.argv[1])} bytes); tile cache hits "
          f"{arena.tile_hits - hits} misses {arena.tile_misses - misses}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
