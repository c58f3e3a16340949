"""A cell's data, made from the seed: relations, filters, pools, tenants.

Tenant ``i`` of a configuration serves relation ``i % relations`` plus
``tenant_records`` records of its own: rows of its relation's pool that
are not records of the relation, drawn from the seed and put into the
tenant's fixup filter. So every tenant answers some rows as no other
tenant does, and a row answered for the wrong tenant, of either
relation, shows in the comparison.

The relations are synthesized and their filters fitted from the seed;
the fixup bitset of each relation is sized for the configuration's
``fixup_capacity`` keys (its own keys plus a tenant's records fit in
it), and each tenant's bitset is its relation's with the tenant's
records inserted.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.lib import filters, quant, relation


def sub_seed(seed: int, *stream: int) -> int:
    """A 32-bit seed for one purpose, derived from the run's seed."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *stream])
    return int(ss.generate_state(1)[0])


def tenant_names(config: Dict) -> List[str]:
    return [f"{config['name']}-{i:04d}"
            for i in range(int(config["serving"]["tenants"]))]


def relation_of(config: Dict):
    k = int(config["relations"])
    return lambda tenant: tenant % k


def make_filter(config: Dict, seed: int, r: int
                ) -> Tuple[relation.Relation, filters.Filter]:
    """Synthesize relation ``r`` and fit its filter; the fixup bitset
    has the configuration's fixed size. A quantized configuration's
    filter is also quantized and calibrated here, once per relation
    (``quant``), and its fixup filter holds every key that the float32
    model rejects at ``tau`` or the quantized one at ``tau_q``."""
    rc, m, t = config["relation"], config["model"], config["train"]
    rel = relation.synthesize(rc["cards"], int(rc["records"]),
                              sub_seed(seed, 1, r), rc["zipf_a"],
                              rc["noise"])
    cols = filters.plan(rc["cards"], int(m["theta"]), int(m["ns"]))
    ids, labels = relation.training_set(
        rel, int(t["n_pos"]), int(t["n_neg"]), sub_seed(seed, 2, r),
        float(t["wildcard_prob"]))
    hidden = int(m["hidden"][0])
    params = filters.train(cols, hidden, ids, labels,
                           steps=int(t["steps"]), batch=int(t["batch_size"]),
                           lr=float(t["learning_rate"]),
                           clip=float(t["grad_clip_norm"]),
                           seed=sub_seed(seed, 3, r))
    # every indexed key: the records and the wildcarded positives
    keys = np.unique(np.concatenate([rel.records, ids[labels > 0.5]]),
                     axis=0)
    key_logits = filters.logits64(params, cols, keys)
    tau = float(m["tau"])
    served = {}
    qm = quant.mode(config)
    if qm is not None:
        qparams = quant.quantize(params, qm)
        deq = quant.dequantize(qparams, filters.param_shapes(cols, hidden),
                               qm)
        tau_q = quant.tau_q(params, deq, cols, qm, tau, sub_seed(seed, 6, r))
        threshold = quant.threshold(tau_q)
        # the fixup filter holds what either model rejects, so the
        # guarantee does not lean on the calibration
        key_logits = np.minimum(
            key_logits, filters.logits64(deq, cols, keys) - threshold)
        served = dict(qparams=qparams, tau_q=tau_q, threshold=threshold,
                      dequantized=deq)
    bits, m_bits, n_hashes, n_keys = filters.build_fixup(
        keys, key_logits, float(t["fixup_fpr"]), int(t["fixup_capacity"]))
    return rel, filters.Filter(
        theta=int(m["theta"]), ns=int(m["ns"]), cols=cols, hidden=hidden,
        params=params, bits=bits, m_bits=m_bits, n_hashes=n_hashes,
        n_keys=n_keys, tau=tau, **served)


def make_pool(rel: relation.Relation, mix: Dict, seed: int, r: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    return relation.query_pool(rel, int(mix["pool_rows"]),
                               sub_seed(seed, 4, r),
                               float(mix["member_share"]),
                               float(mix["wildcard_prob"]))


@dataclasses.dataclass
class Tenants:
    """Per tenant: its relation, its records' pool rows (``own``, sorted)
    and its fixup bitset; ``slot`` numbers the tenants of one relation."""
    relation: np.ndarray           # (n,) relation index
    slot: np.ndarray               # (n,) index among its relation's tenants
    own: np.ndarray                # (n, k) pool row indices
    bits: List[np.ndarray]         # per relation: (tenants of r, words)

    def filter_of(self, t: int, base: filters.Filter) -> filters.Filter:
        return dataclasses.replace(
            base, bits=self.bits[self.relation[t]][self.slot[t]],
            n_keys=base.n_keys + self.own.shape[1])


def make_tenants(config: Dict, filts: List[filters.Filter],
                 pools: List[np.ndarray], is_record: List[np.ndarray],
                 seed: int) -> Tenants:
    n = int(config["serving"]["tenants"])
    k = int(config["serving"]["tenant_records"])
    rel_of = relation_of(config)
    rel = np.asarray([rel_of(t) for t in range(n)])
    slot = np.zeros(n, np.int64)
    own = np.zeros((n, k), np.int64)
    bits = []
    for r, (f, rows, rec) in enumerate(zip(filts, pools, is_record)):
        mine = np.flatnonzero(rel == r)
        slot[mine] = np.arange(len(mine))
        candidates = np.flatnonzero(~rec)
        rb = np.repeat(f.bits[None], len(mine), axis=0)
        rng = np.random.default_rng(sub_seed(seed, 5, r))
        for j, t in enumerate(mine):
            own[t] = np.sort(rng.choice(candidates, k, replace=False))
            words, masks = filters.probe(rows[own[t]], f.m_bits, f.n_hashes)
            np.bitwise_or.at(rb[j], words.reshape(-1), masks.reshape(-1))
        bits.append(rb)
    return Tenants(relation=rel, slot=slot, own=own, bits=bits)


@dataclasses.dataclass
class Reference:
    """The plain reference over the rows of a schedule's requests, back
    to back in schedule order: the answer, whether it hangs on rounding
    (``border``), whether the row is a record of the request's tenant,
    and the distance of its reference logit from the threshold
    (``margin``). ``start[e]`` is where request ``e``'s rows begin."""
    start: np.ndarray
    answers: np.ndarray
    border: np.ndarray
    is_record: np.ndarray
    margin: np.ndarray


def pool_models(filts: List[filters.Filter], pools: List[np.ndarray]
                ) -> List[np.ndarray]:
    """Per relation, the float64 reference logit of every pool row, on
    the weights the program serves (a quantized filter's dequantized in
    float32, as the program dequantizes them)."""
    return [filters.logits64(f.served, f.cols, rows)
            for f, rows in zip(filts, pools)]


def reference(filts: List[filters.Filter], pools: List[np.ndarray],
              is_record: List[np.ndarray], tenants: Tenants, sched,
              logits: List[np.ndarray], block: int = 1 << 21,
              thresholds: Optional[List[float]] = None) -> Reference:
    """The reference answers of every row of ``sched``'s requests, the
    model side read from ``logits`` (per relation, per pool row) against
    each relation's served threshold (``thresholds``, logits: 0 for a
    float32 filter at tau 0.5, ``logit(tau_q)`` for a quantized one);
    ``BORDER_LOGIT`` is measured from that threshold."""
    if thresholds is None:
        thresholds = [f.threshold for f in filts]
    probes = [filters.probe(rows, f.m_bits, f.n_hashes)
              for f, rows in zip(filts, pools)]
    rows_n = np.asarray(sched.rows, np.int64)
    start = np.concatenate([[0], np.cumsum(rows_n)[:-1]]).astype(np.int64)
    total = int(rows_n.sum())
    answers = np.zeros(total, bool)
    border = np.zeros(total, bool)
    rec = np.zeros(total, bool)
    margin = np.zeros(total, np.float32)
    t_req = np.asarray(sched.tenant, np.int64)
    pos = spans(np.asarray(sched.offset, np.int64), rows_n)
    t_row = np.repeat(t_req, rows_n)
    for s in range(0, total, block):
        e = min(total, s + block)
        idx, t = pos[s:e], t_row[s:e]
        r_row = tenants.relation[t]
        for r, (words, masks) in enumerate(probes):
            sel = np.flatnonzero(r_row == r)
            if not len(sel):
                continue
            i, ts = idx[sel], tenants.slot[t[sel]]
            got = tenants.bits[r][ts[:, None], words[i]] & masks[i]
            in_fixup = np.all(got != 0, axis=-1)
            lg = logits[r][i] - thresholds[r]
            answers[s + sel] = (lg >= 0) | in_fixup
            border[s + sel] = (np.abs(lg) < filters.BORDER_LOGIT) \
                & ~in_fixup
            margin[s + sel] = np.abs(lg)
            own = tenants.own[t[sel]]
            mine = np.any(own == i[:, None], axis=-1)
            rec[s + sel] = is_record[r][i] | mine
    return Reference(start=start, answers=answers, border=border,
                     is_record=rec, margin=margin)


def spans(starts: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``concatenate([arange(s, s + n) for s, n in zip(starts, rows)])``."""
    rows = np.asarray(rows, np.int64)
    ends = np.cumsum(rows)
    shift = np.repeat(np.asarray(starts, np.int64) - (ends - rows), rows)
    return np.arange(int(ends[-1]) if len(ends) else 0) + shift
