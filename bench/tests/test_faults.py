"""A run with the timed path broken underneath reads ``correct`` false.

Each case drives the whole harness (set-up, window, reference, check)
on the CPU at a small size, with one fault planted in the grouped
program's output where it is produced (``PlanGroupArena.run``):

* ``flip``: one answer of every batch altered;
* ``wrong_tenant``: every row answered with its neighbouring arena
  slot's filter, which belongs to the other relation;
* ``same_relation_tenant``: every row answered with the filter of the
  slot two along, a tenant of the same relation that differs only in
  its own records;
* ``half_batch``: the second half of every batch left unanswered
  (False).

The sound run, with nothing planted, reads ``correct`` true.
"""
import numpy as np
import pytest

from bench.tests import tiny


def _flip(run):
    def patched(self, raw_ids, tenant_idx):
        ans, model, backup = run(self, raw_ids, tenant_idx)
        ans = np.array(ans)
        ans[0] = ~ans[0]
        return ans, model, backup
    return patched


def _wrong_tenant(run):
    def patched(self, raw_ids, tenant_idx):
        return run(self, raw_ids, np.asarray(tenant_idx) ^ 1)
    return patched


def _same_relation_tenant(run):
    def patched(self, raw_ids, tenant_idx):
        return run(self, raw_ids, np.asarray(tenant_idx) ^ 2)
    return patched


def _half_batch(run):
    def patched(self, raw_ids, tenant_idx):
        out = [np.array(o) for o in run(self, raw_ids, tenant_idx)]
        for o in out:
            o[len(o) // 2:] = False
        return tuple(out)
    return patched


@pytest.fixture(scope="module")
def small():
    return tiny.config(), tiny.mix()


def test_sound_run_is_correct(small):
    out = tiny.measure(*small)
    assert out["correct"] is True
    assert all(v["value"] <= v["limit"] for v in out["checks"].values())
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rows_per_s", "p50_ms", "p90_ms",
                                   "device_mib_per_tenant", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_flip, _wrong_tenant,
                                   _same_relation_tenant, _half_batch])
def test_fault_reads_incorrect(small, monkeypatch, fault):
    from repro.serve_filter.arena import PlanGroupArena
    monkeypatch.setattr(PlanGroupArena, "run", fault(PlanGroupArena.run))
    out = tiny.measure(*small)
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["checks"].values())


def test_closed_loop_is_correct():
    out = tiny.measure(tiny.config(), tiny.mix("bulk-closed"),
                       cell="airplane-t5500.bulk-closed")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rows_per_s", "device_mib_per_tenant",
                                   "setup_s"}
