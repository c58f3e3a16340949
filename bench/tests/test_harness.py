"""The harness is driven by data: every cell finds its files by name.

Checks that every ``workloads`` entry of ``BENCHMARK.json`` finds its
configuration, traffic and per-layer metric files, that names and units
keep to the allowed characters, that the traffic generator is
deterministic under ``--seed``, that a new cell, mix or metric is new
files and entries only, and that the command exits non-zero, with no
result, where there is no TPU or no program.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run
from bench.lib import filters, program, spec, traffic, work

ROOT = run.ROOT
BENCH = run.BENCH


@pytest.fixture(scope="module")
def bench():
    return spec.load(ROOT)


def test_contract_shape(bench):
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert spec.validate(bench) == []
    assert {m["name"] for m in bench["end_to_end"]} <= set(run.E2E)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cfg = spec.config(bench, w["config"], ROOT)
        assert cfg["name"] == w["config"]
        mix = traffic.load(BENCH, w["traffic"], w["config"])
        assert mix["loop"] in ("open", "closed")
        if mix["loop"] == "open":
            assert float(mix["rate_rows_per_s"]) > 0
        for trace in (False, True):
            reported = spec.metrics_for(bench, w["name"], trace)
            assert reported, (w["name"], trace)
            for m in reported:
                if trace:
                    assert callable(spec.reader(m["name"], BENCH))
        names = {m["name"] for m in spec.metrics_for(bench, w["name"],
                                                     False)}
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("name,ok", [
    ("airplane-t5500.fleet-open", True), ("p99_ms", True),
    ("_x", True), ("a b", False), ("a/b", False), ("a,b", False),
    (".x", False), ("x" * 65, False), ("µs", False)])
def test_name_rule(name, ok):
    assert bool(spec.NAME.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("rows/s", True), ("%", True), ("MiB", True), ("us", True),
    ("rows per s", False), ("µs", False), ("x" * 17, False)])
def test_unit_rule(unit, ok):
    assert bool(spec.UNIT.match(unit)) is ok


@pytest.mark.parametrize("name", ["fleet-open", "bulk-closed"])
def test_traffic_is_deterministic_under_seed(name):
    mix = traffic.load(BENCH, name, "airplane-t5500")
    seed = 2 ** 31 + 977
    a = traffic.schedule(mix, 1024, seed, 2.0)
    b = traffic.schedule(mix, 1024, seed, 2.0)
    c = traffic.schedule(mix, 1024, seed + 1, 2.0)
    for f in ("tenant", "rows", "offset", "due"):
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None and y is None) or np.array_equal(x, y)
    # another seed: the same work in another order
    assert not np.array_equal(a.tenant, c.tenant)
    assert np.array_equal(np.sort(a.rows), np.sort(c.rows))
    # ... and meets the same bursts: one sequence, entered elsewhere
    def same_gaps(k):
        if a.due is None:
            return True
        ga = np.roll(np.append(np.diff(a.due), np.nan), -k)[:-1]
        known = ~np.isnan(ga)
        return np.allclose(ga[known], np.diff(c.due)[known])

    shifts = [k for k in np.flatnonzero(a.rows == c.rows[0])
              if np.array_equal(np.roll(a.rows, -k), c.rows)
              and same_gaps(k)]
    assert shifts
    assert np.all(a.offset + a.rows <= mix["pool_rows"])
    if a.due is not None:
        assert a.due[0] == 0 and np.all(np.diff(a.due) >= 0)
        assert a.due[-1] < 2.0


def test_open_loop_offers_its_rate():
    mix = dict(traffic.load(BENCH, "fleet-open", "airplane-t5500"),
               rate_rows_per_s=1e6)
    s = traffic.schedule(mix, 1024, 5, 10.0)
    assert abs(s.rows.sum() / 10.0 - 1e6) / 1e6 < 1e-3
    assert np.median(s.rows) == 16
    # YCSB zipfian 0.99: the hottest of 1024 tenants takes ~13%
    top = np.bincount(s.tenant, minlength=1024).max() / len(s)
    assert 0.11 < top < 0.15


def test_new_entries_need_no_edit(tmp_path, bench):
    """A later cell with a new mix and a new metric: files and entries
    only, found by name."""
    shutil.copytree(BENCH, tmp_path / "bench")
    nb = tmp_path / "bench"
    with open(nb / "traffic" / "fleet-burst.json", "w") as f:
        json.dump(dict(traffic.load(BENCH, "fleet-open", "x"),
                       rate_rows_per_s=1000.0), f)
    (nb / "metrics" / "queue_wait_p99_ms.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "airplane-t5500.fleet-burst",
                           "config": "airplane-t5500",
                           "traffic": "fleet-burst", "chips": 1,
                           "why": "bursts"})
    b["per_layer"].append({"name": "queue_wait_p99_ms.burst", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "scheduler (serve_filter/scheduler.py)",
                           "moves": "p90_ms",
                           "workloads": ["airplane-t5500.fleet-burst"]})
    # a configuration served from int4-NF4 arenas, with its own
    # fleet-open rate
    qname = "airplane-t5500-int4nf4"
    qcfg = dict(spec.config(bench, "airplane-t5500", ROOT), name=qname)
    qcfg["serving"] = dict(qcfg["serving"], dtype="int4-nf4", quant={
        "row_group": 32, "calib_samples": 512, "margin_safety": 2.0,
        "margin_floor": 1e-3})
    with open(nb / "configs" / f"{qname}.json", "w") as f:
        json.dump(qcfg, f)
    with open(nb / "traffic" / "fleet-open" / f"{qname}.json", "w") as f:
        json.dump({"rate_rows_per_s": 300000}, f)
    b["configs"].append(dict(b["configs"][0], name=qname,
                             file=f"bench/configs/{qname}.json"))
    b["workloads"].append({"name": f"{qname}.fleet-open", "config": qname,
                           "traffic": "fleet-open", "chips": 1,
                           "why": "int4-NF4 arenas"})
    assert spec.validate(b) == []
    mix = traffic.load(str(nb), "fleet-burst", "airplane-t5500")
    assert mix["rate_rows_per_s"] == 1000.0
    names = [m["name"] for m in spec.metrics_for(
        b, "airplane-t5500.fleet-burst", True)]
    assert names == ["queue_wait_p99_ms.burst"]
    assert spec.reader(names[0], str(nb))({}) == 1.0
    cell = spec.workload(b, f"{qname}.fleet-open")
    cfg = spec.config(b, cell["config"], str(tmp_path))
    assert cfg == qcfg
    assert traffic.load(str(nb), "fleet-open", qname)[
        "rate_rows_per_s"] == 300000
    q = program.serve_config(cfg, trace=False).quant
    assert (q.enabled, q.bits, q.grid, q.row_group) == (True, 4, "nf4", 32)
    assert work.per_row(cfg)["bytes"] == 125.5


def test_quantized_configuration_states_its_quant_block(bench):
    cfg = spec.config(bench, "airplane-t5500", ROOT)
    cfg["serving"] = dict(cfg["serving"], dtype="int8")
    with pytest.raises(ValueError, match="states serving.quant"):
        program.serve_config(cfg, trace=False)
    cfg["serving"]["dtype"] = "bfloat16"
    with pytest.raises(ValueError, match="none of float32"):
        program.serve_config(cfg, trace=False)
    cfg["serving"] = dict(cfg["serving"], dtype="float32", grouped=False)
    with pytest.raises(ValueError, match="grouped"):
        program.serve_config(cfg, trace=False)


# the checkpoint of a fixed small float32 filter as the harness wrote it
# before quantized configurations: each array's CRC-32, and the CRC-32
# of the index's meta (key-sorted JSON)
PARENT_CKPT_CRC = {
    "['fixup_bits']": 307507916,
    "['params']['dense']['b0']": 1538376245,
    "['params']['dense']['b_out']": 3384322503,
    "['params']['dense']['w0']": 3870195317,
    "['params']['dense']['w_out']": 610781158,
    "['params']['embed']['col0']": 2796057447,
    "['params']['embed']['col1']": 3610764301,
    "['params']['embed']['col2']": 2786897978,
    "['params']['embed']['col3']": 2805701297,
    "['params']['embed']['col4']": 1219548011,
    "['params']['embed']['col5']": 2601817352,
    "['params']['embed']['col6']": 4200174231,
    "['params']['embed']['col7']": 3484168991,
    "['params']['embed']['col8']": 2488565066,
    "['params']['embed']['col9']": 129008568,
}
PARENT_META_CRC = 956113159


def test_float32_checkpoint_is_unchanged(tmp_path):
    import zlib
    cols = filters.plan([50, 60, 40, 30, 20, 25, 10], 30, 2)
    rng = np.random.default_rng(20261019)
    params = {g: {k: rng.standard_normal(s).astype(np.float32)
                  for k, s in d.items()}
              for g, d in filters.param_shapes(cols, 8).items()}
    m_bits, n_hashes = filters.bloom_size(100, 0.01)
    bits = rng.integers(0, 2 ** 32, (m_bits + 31) // 32, dtype=np.uint32)
    program.save_filter(str(tmp_path), filters.Filter(
        theta=30, ns=2, cols=cols, hidden=8, params=params, bits=bits,
        m_bits=m_bits, n_hashes=n_hashes, n_keys=17, tau=0.5))
    with open(tmp_path / "step_0" / "meta.json") as f:
        meta = json.load(f)
    assert {k: v["crc32"] for k, v in meta["arrays"].items()} \
        == PARENT_CKPT_CRC
    assert zlib.crc32(json.dumps(meta["extra"], sort_keys=True).encode()) \
        == PARENT_META_CRC


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "airplane-t5500.fleet-open", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert "no src/repro" in p.stderr
    assert p.stdout.strip() == ""
