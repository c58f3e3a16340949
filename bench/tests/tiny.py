"""A cell at a size a CPU test run can hold, driven through the harness."""
import argparse
import copy

from bench import run
from bench.lib import spec, traffic

SEED = 2 ** 31 + 4242


# serving.quant at the program's defaults, but for row groups of 4 rows,
# so that the small tables hold several
QUANT = {"row_group": 4, "calib_samples": 512, "margin_safety": 2.0,
         "margin_floor": 1e-3}


def config(name="airplane-t5500", tenants=8, dtype="float32"):
    """The configuration with its relation cut to a few thousand
    records over small columns and a short fit, served as ``dtype``."""
    cfg = copy.deepcopy(spec.config(spec.load(run.ROOT), name, run.ROOT))
    cfg["relation"] = dict(cfg["relation"], cards=[50, 60, 40, 30, 20, 25,
                                                   10], records=2000)
    cfg["model"] = dict(cfg["model"], theta=30)
    cfg["train"] = dict(cfg["train"], steps=50, n_pos=2000, n_neg=2000,
                        fixup_capacity=2000)
    cfg["serving"] = dict(cfg["serving"], tenants=tenants, tenant_records=16)
    if dtype != "float32":
        cfg["serving"].update(dtype=dtype, quant=dict(QUANT))
    return cfg


def mix(name="fleet-open"):
    m = traffic.load(run.BENCH, name, "airplane-t5500")
    if m["loop"] == "open":
        return dict(m, rate_rows_per_s=20000, pool_rows=4096)
    return dict(m, pool_rows=8192, requests=64)


def measure(cfg, m, cell="airplane-t5500.fleet-open", seconds=0.5):
    """One run of the harness past its look for a chip: the result."""
    import jax
    bench = spec.load(run.ROOT)
    reported = spec.metrics_for(bench, cell, False)
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds,
                              trace=0)
    return run.measure(jax, args, cfg, m, reported, {}, {},
                       run.Lowerings(jax))
