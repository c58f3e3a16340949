"""End-to-end existence index: C-LMBF/LMBF model + fixup filter.

``ExistenceIndex.fit`` trains the classifier on sampled positives/negatives,
builds the fixup filter from residual false negatives, and exposes
``query`` with the Bloom-filter contract: **no false negatives** on the
indexed positives (property-tested in tests/test_existence.py).

The query pipeline itself is the pure function :func:`query_stages` —
``encode -> embed -> MLP -> tau threshold -> fixup Bloom probe`` in one
jittable program — which the serving subsystem (``repro.serve_filter``)
compiles per (plan-shape, batch-bucket). A fitted index round-trips
through ``checkpoint.manager`` via :func:`save_index` / :func:`load_index`
(arrays in the npz payload, plan/config/tau in the JSON meta).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.core import bloom, compression as comp, fixup, lmbf, memory
from repro.data import tuples as tuples_lib
from repro.nn import abstract_params
from repro.optim import Adam
from repro.runtime.trace import NULL_TRACER, Tracer


@dataclasses.dataclass
class TrainSettings:
    steps: int = 600
    batch_size: int = 512
    learning_rate: float = 1e-2
    tau: float = 0.5
    fixup_fpr: float = 0.01
    seed: int = 0
    wildcard_prob: float = 0.2
    n_pos: int = 20_000
    n_neg: int = 20_000


def query_stages(params, cfg: lmbf.LMBFConfig, tau, fixup_bits,
                 fixup_params: Optional[bloom.BloomParams], raw_ids, *,
                 probe_fn=None, predict_fn=None):
    """The whole query pipeline as ONE jittable program.

    ``compression.encode -> embedding gather -> MLP -> tau threshold ->
    fixup Bloom probe`` with no host round-trips between stages. ``cfg``
    and ``fixup_params`` are hashable (frozen dataclasses) and must be
    static under ``jax.jit``; ``tau`` may be traced — a scalar, or a
    per-row vector when one dispatch carries many tenants' rows — so
    filters sharing a plan shape share one compiled program.
    ``probe_fn(bits, ids)`` overrides the fixup probe (the serving
    subsystem injects the ``kernels/bloom_query`` Pallas kernel, or a
    grouped per-row-offset probe, here; ``fixup_params`` may then be
    ``None`` — a grouped dispatch has no single filter geometry);
    ``predict_fn(params, cfg, enc)`` overrides the model score (the
    sharded executor injects a masked-gather + psum variant over
    vocab-sharded tables, the grouped executor a stacked-arena gather).

    Returns ``(answers, model_yes, backup_yes)`` — the per-stage booleans
    feed the serving subsystem's stage-FPR counters.
    """
    raw_ids = jnp.asarray(raw_ids, jnp.int32)
    enc = comp.encode(raw_ids, cfg.plan)
    s = (predict_fn or lmbf.predict)(params, cfg, enc)
    model_yes = s >= tau
    if probe_fn is None:
        backup_yes = bloom.query(fixup_bits, raw_ids, fixup_params)
    else:
        backup_yes = probe_fn(fixup_bits, raw_ids)
    return model_yes | backup_yes, model_yes, backup_yes


class QuantConfigMismatch(ValueError):
    """A quantized (``existence_index_v3``) checkpoint payload was asked
    to serve under a DIFFERENT quantization mode than it was packed for.
    The packed codes are meaningless on another grid/width, so serving
    them would produce garbage answers; hydration must fail loudly (and
    non-transiently — no retry can fix a config mismatch) instead."""


@dataclasses.dataclass
class ExistenceIndex:
    cfg: lmbf.LMBFConfig
    params: object
    fixup_filter: fixup.FixupFilter
    tau: float
    train_log: dict
    # lazily-populated quantized serving state (see ensure_quant_state):
    # {"meta": quant-mode dict, "qparams": packed tree, "tau": calibrated
    #  threshold, "pinned": bool — True iff loaded from a v3 checkpoint}
    quant_cache: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)

    def scores(self, raw_ids) -> jax.Array:
        enc = comp.encode(jnp.asarray(raw_ids, jnp.int32), self.cfg.plan)
        return lmbf.predict(self.params, self.cfg, enc)

    def query(self, raw_ids) -> jax.Array:
        """(n, n_cols) raw ids -> (n,) bool membership answers."""
        ans, _, _ = query_stages(
            self.params, self.cfg, self.tau,
            jnp.asarray(self.fixup_filter.bits),
            self.fixup_filter.params, raw_ids)
        return ans

    @property
    def memory(self) -> memory.ModelMemory:
        return memory.accounting(self.cfg)

    @property
    def total_mb(self) -> float:
        return self.memory.weights_mb + self.fixup_filter.size_mb


def fit(ds: tuples_lib.TupleDataset, theta: int, ns: int = 2,
        hidden: Tuple[int, ...] = (64,), onehot_max: int = 0,
        settings: Optional[TrainSettings] = None) -> ExistenceIndex:
    st = settings or TrainSettings()
    plan = comp.make_plan(ds.cards, theta=theta, ns=ns)
    cfg = lmbf.LMBFConfig(plan=plan, hidden=hidden, onehot_max=onehot_max)

    ids, labels = tuples_lib.make_training_set(
        ds, st.n_pos, st.n_neg, st.seed, st.wildcard_prob)
    enc = comp.encode_np(ids, plan)

    key = jax.random.key(st.seed)
    params = lmbf.init(cfg, key)
    opt = Adam(learning_rate=st.learning_rate, grad_clip_norm=1.0)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch_ids, batch_labels):
        loss, grads = jax.value_and_grad(lmbf.bce_loss)(
            params, cfg, batch_ids, batch_labels)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    rng = np.random.default_rng(st.seed + 7)
    t0 = time.perf_counter()
    losses = []
    n = len(enc)
    for i in range(st.steps):
        sel = rng.integers(0, n, size=min(st.batch_size, n))
        params, opt_state, loss = step(
            params, opt_state, jnp.asarray(enc[sel]),
            jnp.asarray(labels[sel]))
        if i % 50 == 0 or i == st.steps - 1:
            losses.append((i, float(loss)))
    train_s = time.perf_counter() - t0

    # fixup from ALL indexed positives (wildcard-free records + sampled
    # wildcard variants used in training)
    pos_mask = labels > 0.5
    pos_ids = ids[pos_mask]
    all_pos = np.concatenate([ds.records, pos_ids], axis=0)
    all_pos = np.unique(all_pos, axis=0)
    scores = np.asarray(lmbf.predict(
        params, cfg, jnp.asarray(comp.encode_np(all_pos, plan))))
    fx = fixup.build(all_pos, scores, st.tau, st.fixup_fpr)

    # held-out accuracy (fresh positives + negatives)
    test_ids, test_labels = tuples_lib.make_training_set(
        ds, 4096, 4096, st.seed + 1000, st.wildcard_prob)
    test_scores = np.asarray(lmbf.predict(
        params, cfg, jnp.asarray(comp.encode_np(test_ids, plan))))
    acc = float(np.mean((test_scores >= st.tau) == (test_labels > 0.5)))

    return ExistenceIndex(
        cfg=cfg, params=params, fixup_filter=fx, tau=st.tau,
        train_log={"losses": losses, "train_seconds": train_s,
                   "accuracy": acc,
                   "fn_count": fx.n_false_negatives,
                   "steps": st.steps})


# ------------------------------------------------- quantized serving state

def ensure_quant_state(idx: ExistenceIndex, qmeta: Dict):
    """``(qparams, calibrated_tau)`` for serving ``idx`` under the
    quantization mode ``qmeta`` (the dict form of a serve-side
    QuantConfig: bits/grid/row_group/calib_samples/margin_safety/
    margin_floor), computed at most once per mode per index.

    The result is cached on ``idx.quant_cache``. A cache hydrated from
    an ``existence_index_v3`` checkpoint is ``pinned``: requesting a
    different mode raises :class:`QuantConfigMismatch` — the packed
    payload only decodes on its own grid, and the caller chose a v3
    checkpoint precisely to skip requantize+calibrate. An in-memory
    (unpinned) cache for another mode is silently recomputed.
    """
    qmeta = {"bits": int(qmeta["bits"]), "grid": str(qmeta["grid"]),
             "row_group": int(qmeta["row_group"]),
             "calib_samples": int(qmeta["calib_samples"]),
             "margin_safety": float(qmeta["margin_safety"]),
             "margin_floor": float(qmeta["margin_floor"])}
    cached = getattr(idx, "quant_cache", None)
    if cached is not None:
        if cached["meta"] == qmeta:
            return cached["qparams"], cached["tau"]
        if cached.get("pinned"):
            raise QuantConfigMismatch(
                f"checkpoint quantized as {cached['meta']} cannot serve "
                f"under {qmeta}; re-save the index for the new mode or "
                f"hydrate from an fp32 (v2) checkpoint")
    qp = lmbf.quantize_params(idx.params, idx.cfg,
                              row_group=qmeta["row_group"],
                              bits=qmeta["bits"], grid=qmeta["grid"])
    tau_q = lmbf.calibrated_tau(
        idx.params, qp, idx.cfg, idx.tau, row_group=qmeta["row_group"],
        n_samples=qmeta["calib_samples"], safety=qmeta["margin_safety"],
        floor=qmeta["margin_floor"], bits=qmeta["bits"],
        grid=qmeta["grid"])
    idx.quant_cache = {"meta": qmeta, "qparams": qp, "tau": tau_q,
                       "pinned": False}
    return qp, tau_q


# ------------------------------------------------------- (de)serialization

def _plan_to_json(plan: comp.CompressionPlan) -> Dict:
    return {
        "theta": plan.theta, "ns": plan.ns,
        "columns": [{"v": c.v, "ns": c.ns,
                     "divisors": list(c.divisors),
                     "sub_cards": list(c.sub_cards)}
                    for c in plan.columns],
    }


def _plan_from_json(d: Dict) -> comp.CompressionPlan:
    cols = tuple(comp.ColumnPlan(
        v=int(c["v"]), ns=int(c["ns"]),
        divisors=tuple(int(x) for x in c["divisors"]),
        sub_cards=tuple(int(x) for x in c["sub_cards"]))
        for c in d["columns"])
    return comp.CompressionPlan(columns=cols, theta=int(d["theta"]),
                                ns=int(d["ns"]))


# Checkpoint kinds this module can hydrate. v1 indexes were fit when
# mlp_head's output layer was a (prev, 1) GEMV; it is now a
# multiply+reduce (required so grouped serving can reproduce it batched
# bit-for-bit), whose float accumulation differs in the last ulps — a
# v1 index's borderline rows near tau can flip, and flipped members are
# NOT covered by its fixup filter. Loading v1 therefore warns: refit to
# restore the no-false-negative guarantee. v3 = v2 plus the quantized
# serving payload (packed codes + scales + calibrated tau), so hydrating
# a quantized plan skips requantize+calibrate entirely.
_INDEX_KINDS = ("existence_index_v3", "existence_index_v2",
                "existence_index_v1")


def index_meta(idx: ExistenceIndex, kind: str = "existence_index_v2") -> Dict:
    """JSON-safe description of everything but the arrays."""
    return {
        "kind": kind,
        "plan": _plan_to_json(idx.cfg.plan),
        "hidden": list(idx.cfg.hidden),
        "onehot_max": idx.cfg.onehot_max,
        "dtype": str(jnp.dtype(idx.cfg.dtype)),
        "tau": float(idx.tau),
        "fixup": {"m_bits": idx.fixup_filter.params.m_bits,
                  "n_hashes": idx.fixup_filter.params.n_hashes,
                  "n_false_negatives": idx.fixup_filter.n_false_negatives},
        "train_log": idx.train_log,
    }


def config_from_meta(meta: Dict) -> lmbf.LMBFConfig:
    return lmbf.LMBFConfig(
        plan=_plan_from_json(meta["plan"]),
        hidden=tuple(int(h) for h in meta["hidden"]),
        onehot_max=int(meta["onehot_max"]),
        dtype=jnp.dtype(meta["dtype"]))


def _abstract_qparams(cfg: lmbf.LMBFConfig, qmeta: Dict) -> Dict:
    """ShapeDtypeStruct tree of a v3 checkpoint's quantized payload —
    derivable from config + quant meta alone, so restore never trusts
    payload shapes."""
    bits, rg = int(qmeta["bits"]), int(qmeta["row_group"])
    qdt = jnp.uint8 if bits == 4 else jnp.int8
    tree = {"embed": {}, "embed_scale": {}, "dense": {}, "dense_scale": {}}
    for i, (rows, e) in enumerate(cfg.column_encodings):
        if e is None:
            continue
        w = lmbf.packed_dim(e) if bits == 4 else e
        tree["embed"][f"col{i}"] = jax.ShapeDtypeStruct((rows, w), qdt)
        tree["embed_scale"][f"col{i}"] = jax.ShapeDtypeStruct(
            (-(-rows // rg),), jnp.float32)
    dims = lmbf.dense_in_dims(cfg)
    for name, spec in lmbf.params_spec(cfg)["dense"].items():
        if name.startswith("b"):
            tree["dense"][name] = jax.ShapeDtypeStruct(
                spec.shape, jnp.float32)
            continue
        d_in = lmbf.packed_dim(dims[name]) if bits == 4 else dims[name]
        tree["dense"][name] = jax.ShapeDtypeStruct(
            (d_in,) + tuple(spec.shape[1:]), qdt)
        tree["dense_scale"][name] = jax.ShapeDtypeStruct(
            tuple(spec.shape[1:]), jnp.float32)
    return tree


def save_index(directory: str, idx: ExistenceIndex, *, step: int = 0,
               keep: int = 3, quant: Optional[Dict] = None) -> None:
    """Persist a fitted index through the checkpoint manager (atomic,
    keep-N). Arrays (model params + fixup bitset) land in the npz
    payload; the plan/config/tau ride in the JSON meta.

    With ``quant`` (a quant-mode dict, see :func:`ensure_quant_state`)
    the checkpoint is written as ``existence_index_v3``: the packed
    codes + scales land in the payload alongside the fp32 params (kept
    so direct queries and fp32 plans still hydrate the same file) and
    the calibrated tau rides in the meta — a quantized plan reloading
    this file skips quantization AND calibration entirely."""
    tree = {"params": idx.params,
            "fixup_bits": np.asarray(idx.fixup_filter.bits)}
    if quant is None:
        ckpt.save(directory, step, tree, extra=index_meta(idx), keep=keep)
        return
    qp, tau_q = ensure_quant_state(idx, quant)
    tree["quant"] = qp
    meta = index_meta(idx, kind="existence_index_v3")
    meta["quant"] = dict(idx.quant_cache["meta"], tau_q=float(tau_q))
    ckpt.save(directory, step, tree, extra=meta, keep=keep)


def load_index(directory: str, step: Optional[int] = None, *,
               tracer: Tracer = NULL_TRACER) -> ExistenceIndex:
    """Rebuild a fitted :class:`ExistenceIndex` written by
    :func:`save_index`. ``tracer`` records two ``detail`` spans:
    ``restore`` (the checkpoint read, its CRC checks and the arrays'
    restore) and, for a v3 checkpoint, ``quant_state`` (binding its
    codes, scales and ``tau_q`` as the index's quant cache)."""
    with tracer.span("restore", cat="detail"):
        idx, meta, tree = _restore_index(directory, step)
    if meta["kind"] == "existence_index_v3":
        with tracer.span("quant_state", cat="detail"):
            qmeta = meta["quant"]
            idx.quant_cache = {
                "meta": {"bits": int(qmeta["bits"]),
                         "grid": str(qmeta["grid"]),
                         "row_group": int(qmeta["row_group"]),
                         "calib_samples": int(qmeta["calib_samples"]),
                         "margin_safety": float(qmeta["margin_safety"]),
                         "margin_floor": float(qmeta["margin_floor"])},
                "qparams": jax.tree_util.tree_map(np.asarray,
                                                  tree["quant"]),
                "tau": float(qmeta["tau_q"]),
                "pinned": True,
            }
    return idx


def _restore_index(directory: str, step: Optional[int]):
    """The index of a checkpoint with no quant cache bound yet, its
    meta and the restored tree."""
    if step is None:
        step = ckpt.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {directory}")
    meta = ckpt.read_meta(directory, step)["extra"]
    if meta.get("kind") not in _INDEX_KINDS:
        raise ValueError(f"{directory} step {step} is not an existence "
                         f"index checkpoint: {meta.get('kind')!r}")
    if meta["kind"] == "existence_index_v1":
        warnings.warn(
            f"{directory} step {step} was fit under the pre-grouped MLP "
            "head (existence_index_v1); its scores differ in the last "
            "ulps under the current head, so rows borderline at tau may "
            "flip and the no-false-negative guarantee is not assured — "
            "refit and re-save to upgrade", UserWarning, stacklevel=3)
    cfg = config_from_meta(meta)
    bp = bloom.BloomParams(m_bits=int(meta["fixup"]["m_bits"]),
                           n_hashes=int(meta["fixup"]["n_hashes"]))
    abstract = {
        "params": abstract_params(lmbf.params_spec(cfg)),
        "fixup_bits": jax.ShapeDtypeStruct((bp.n_words,), jnp.uint32),
    }
    if meta["kind"] == "existence_index_v3":
        abstract["quant"] = _abstract_qparams(cfg, meta["quant"])
    tree = ckpt.restore(directory, step, abstract)
    fx = fixup.FixupFilter(
        params=bp, bits=np.asarray(tree["fixup_bits"]),
        n_false_negatives=int(meta["fixup"]["n_false_negatives"]))
    idx = ExistenceIndex(cfg=cfg, params=tree["params"], fixup_filter=fx,
                         tau=float(meta["tau"]), train_log=meta["train_log"])
    return idx, meta, tree


def load_fixup_only(directory: str, step: Optional[int] = None
                    ) -> Tuple[lmbf.LMBFConfig, fixup.FixupFilter]:
    """Load ONLY the fixup/backup Bloom structure of a saved index.

    The degraded serving path: when the model arrays are unreadable
    (corruption, repeated transient failures) the fixup bitset alone
    still answers conservatively — it is a selective, CRC-verified read
    of the one ``fixup_bits`` array, so a fault confined to the model
    payload does not take the tenant down with it."""
    if step is None:
        step = ckpt.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {directory}")
    meta = ckpt.read_meta(directory, step)["extra"]
    if meta.get("kind") not in _INDEX_KINDS:
        raise ValueError(f"{directory} step {step} is not an existence "
                         f"index checkpoint: {meta.get('kind')!r}")
    cfg = config_from_meta(meta)
    bp = bloom.BloomParams(m_bits=int(meta["fixup"]["m_bits"]),
                           n_hashes=int(meta["fixup"]["n_hashes"]))
    key = "['fixup_bits']"   # the keystr path of the saved tree leaf
    host = ckpt.restore_arrays(directory, step, only=(key,))
    bits = np.ascontiguousarray(host[key].astype(np.uint32))
    fx = fixup.FixupFilter(
        params=bp, bits=bits,
        n_false_negatives=int(meta["fixup"]["n_false_negatives"]))
    return cfg, fx
