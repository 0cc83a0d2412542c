"""Prediction & attribution phase — paper §3.5, as matrix algebra.

Inputs per application: profiled op counts (``core.opcount``), execution
time, and memory counters (HBM/VMEM bytes — the cache-hit-rate analogue).
Output: total energy plus a fine-grained breakdown by op class and by
micro-architectural bucket, with const/static separated — the artifact the
case studies (§5.3) consume.

The paper's linear model (Eq. 3, ``E = Σ units_i · energy_i``) is a dot
product over the op-class space, and this module computes it as one: the
``TablePredictor`` resolves the bound table into dense energy vectors over
``isa.CLASS_INDEX``, a single prediction is ``units · e``, and a batch
(``predict_batch``) is one ``C @ e``-style pass over a stacked counts
matrix.  Both paths run the identical kernel, so batched totals are
bitwise-equal to per-program totals.
"""
from __future__ import annotations

import functools
import importlib.util
import warnings
from typing import Dict, List, Mapping, Optional, Sequence, Union

import numpy as np

from repro.core import isa
from repro.core.counting import counts_matrix
# the accumulation core defines OpCounts; importing it from there (not the
# jax front-end ``core.opcount``) keeps this module importable in processes
# without jax — telemetry shard workers price windows through it
from repro.core.counting import OpCounts
from repro.core.table import EnergyTable

# How predicted traffic is split when no profiled counters are available
# (pure static prediction from a lowered program).
_DEFAULT_HBM_BOUNDARY_FRAC = 0.85
_DEFAULT_FUSED_LEAK = 0.05


class Prediction:
    """One workload's energy prediction + attribution.

    ``by_class``/``by_bucket`` are materialized lazily from the underlying
    per-class energy vector (``class_energy_vec``), so fleet-scale batched
    prediction never pays for breakdown dicts nobody reads.
    """

    __slots__ = ("total_j", "const_j", "static_j", "dynamic_j", "coverage",
                 "duration_s", "_by_class", "_by_bucket", "_class_vec",
                 "_bucket_vec")

    def __init__(self, total_j: float, const_j: float, static_j: float,
                 dynamic_j: float,
                 by_class: Optional[Mapping[str, float]] = None,
                 by_bucket: Optional[Mapping[str, float]] = None,
                 coverage: float = 1.0, duration_s: float = 0.0, *,
                 class_vec: Optional[np.ndarray] = None,
                 bucket_vec: Optional[np.ndarray] = None):
        self.total_j = float(total_j)
        self.const_j = float(const_j)
        self.static_j = float(static_j)
        self.dynamic_j = float(dynamic_j)
        self.coverage = float(coverage)   # energy-weighted direct fraction
        self.duration_s = float(duration_s)
        self._by_class = dict(by_class) if by_class is not None else None
        self._by_bucket = dict(by_bucket) if by_bucket is not None else None
        self._class_vec = class_vec
        self._bucket_vec = bucket_vec    # dynamic J over isa.BUCKET_ORDER
        if self._class_vec is None and self._by_class is None:
            self._by_class = {}

    # -- breakdowns ---------------------------------------------------------
    @property
    def class_energy_vec(self) -> np.ndarray:
        """Per-class dynamic joules over ``isa.CLASS_INDEX`` ids."""
        if self._class_vec is None:
            items = list((self._by_class or {}).items())
            ids = [isa.CLASS_INDEX.intern(cls) for cls, _ in items]
            v = np.zeros(len(isa.CLASS_INDEX))
            if ids:
                v[ids] = [e for _, e in items]
            self._class_vec = v
        return self._class_vec

    @property
    def by_class(self) -> Dict[str, float]:
        if self._by_class is None:
            v = self._class_vec
            name = isa.CLASS_INDEX.name
            self._by_class = {name(int(i)): float(v[i])
                              for i in np.nonzero(v)[0]}
        return self._by_class

    @property
    def by_bucket(self) -> Dict[str, float]:
        if self._by_bucket is None:
            out: Dict[str, float] = {}
            if self._bucket_vec is not None:
                out = {isa.BUCKET_ORDER[i]: float(s)
                       for i, s in enumerate(self._bucket_vec) if s != 0.0}
            elif self._class_vec is not None:
                v = self._class_vec
                if v.size:
                    codes = isa.CLASS_INDEX.bucket_codes(v.size)
                    sums = np.bincount(codes, weights=v,
                                       minlength=len(isa.BUCKET_ORDER))
                    out = {isa.BUCKET_ORDER[i]: float(s)
                           for i, s in enumerate(sums) if s != 0.0}
            else:
                for cls, e in (self._by_class or {}).items():
                    b = isa.bucket_of(cls) or isa.UNKNOWN_BUCKET
                    out[b] = out.get(b, 0.0) + e
            out["static"] = self.static_j
            out["const"] = self.const_j
            self._by_bucket = out
        return self._by_bucket

    def top_classes(self, k: int = 10):
        return sorted(self.by_class.items(), key=lambda kv: -kv[1])[:k]

    def __repr__(self) -> str:
        return (f"Prediction(total_j={self.total_j:.4g}, "
                f"dynamic_j={self.dynamic_j:.4g}, "
                f"coverage={self.coverage:.3f}, "
                f"duration_s={self.duration_s:.4g})")


def traffic_from_counts(counts: OpCounts) -> Dict[str, float]:
    """Static traffic estimate when no profiled counters exist (dry-run path)."""
    f = _DEFAULT_HBM_BOUNDARY_FRAC
    leak = counts.fused_bytes * _DEFAULT_FUSED_LEAK
    return {
        "hbm_read_bytes": counts.boundary_read_bytes * f + 0.5 * leak,
        "hbm_write_bytes": counts.boundary_write_bytes * f + 0.5 * leak,
        "vmem_read_bytes": counts.boundary_read_bytes * (1 - f),
        "vmem_write_bytes": counts.boundary_write_bytes * (1 - f),
    }


def _is_point_sequence(op) -> bool:
    """True when ``op`` is a per-job sequence of operating points rather
    than one point: a bare ``(freq, cap)`` pair of scalars is one point."""
    if op is None or hasattr(op, "freq_mhz") or isinstance(op, (str, bytes)):
        return False
    if not isinstance(op, Sequence):
        return False
    if len(op) == 2 and all(x is None or isinstance(x, (int, float))
                            for x in op):
        return False
    return True


_COUNTER_TO_CLASS = {
    "hbm_read_bytes": "hbm.read",
    "hbm_write_bytes": "hbm.write",
    "vmem_read_bytes": "vmem.read",
    "vmem_write_bytes": "vmem.write",
}
_COUNTER_CLASSES = frozenset(_COUNTER_TO_CLASS.values())
_COUNTER_ITEMS = tuple(_COUNTER_TO_CLASS.items())
# counter classes are canonical -> their ids are fixed at import time
_COUNTER_IDS = np.asarray([isa.CLASS_INDEX.intern(c)
                           for c in _COUNTER_TO_CLASS.values()])

# below this batch size the XLA dispatch overhead exceeds the whole plain
# computation; the fused predictor silently uses the plain path (bitwise
# the same either way, so the switch is invisible)
_FUSED_MIN_JOBS = 32


def _build_fused_kernel():
    """Jitted fused hot path (lazy: the only jax import in this module).

    One XLA computation produces both elementwise energy products (direct
    and pred vectors share a single pass over the counts matrix) and the
    per-bucket reduction that ``Prediction.by_bucket`` otherwise recomputes
    per row with ``np.bincount``.  Only *elementwise* work runs under XLA
    — an IEEE multiply is the same bits everywhere — while the row
    reductions that define totals stay in numpy, so the fused path is
    bitwise-identical to the plain one.  Runs under ``jax.enable_x64`` (the
    thread-local flag, not the global config) so float64 counts are not
    silently downcast, and on the host CPU backend even where an
    accelerator is attached: the inputs are zero-copy views of host numpy
    buffers, and only the CPU backend multiplies float64 with IEEE bits
    (TPUs emulate it).
    """
    import jax

    host = jax.devices("cpu")[0]

    @functools.partial(jax.jit, static_argnames=("direct_mode", "n_buckets"))
    def _kernel(c_mat, e_direct, e_pred, codes, mem, ids, *,
                direct_mode, n_buckets):
        # one traversal of the counts matrix feeds both products, the
        # counter-column fold and the bucket reduction; XLA fuses it all
        vd = c_mat * e_direct
        vp = c_mat * e_pred
        val, other = (vd, vp) if direct_mode else (vp, vd)
        e = e_direct if direct_mode else e_pred
        # counter columns folded on device: still exactly one IEEE add per
        # element, the same bits as the plain path's ``val[:, ci] += v``
        vfin = val.at[:, ids].add(mem * e[ids])
        # bucket bincount as a one-hot matmul: (jobs x classes) @
        # (classes x buckets), no transposes materialized
        buckets = vfin @ jax.nn.one_hot(codes, n_buckets, dtype=val.dtype)
        return val, vfin, other, buckets

    def _view(a):
        """Zero-copy numpy view of a host jax buffer."""
        return np.from_dlpack(a)

    def _feed(a):
        """Zero-copy numpy -> host jax import (device_put copies; dlpack
        not).  DLPack cannot export a read-only array: that one is copied."""
        try:
            return jax.dlpack.from_dlpack(a)
        except BufferError:
            return jax.device_put(a, host)

    feeds: dict = {}

    def _feed_cached(a):
        """Identity-keyed feed cache for call-stable arrays (the energy
        vectors and bucket codes persist across calls until the table is
        invalidated; re-exporting them every call is pure overhead).
        Holding ``a`` in the entry keeps its id() valid while cached."""
        hit = feeds.get(id(a))
        if hit is not None and hit[0] is a:
            return hit[1]
        j = _feed(a)
        if len(feeds) > 12:
            feeds.clear()
        feeds[id(a)] = (a, j)
        return j

    def run(c_mat, e_direct, e_pred, codes, mem, direct_mode, n_buckets):
        with jax.enable_x64(True):
            val, vfin, other, buckets = _kernel(
                _feed(c_mat), _feed_cached(e_direct), _feed_cached(e_pred),
                _feed_cached(codes), _feed(mem), _feed_cached(_COUNTER_IDS),
                direct_mode=direct_mode, n_buckets=n_buckets)
        # everything comes back as zero-copy read-only views; retained
        # Predictions copy their own rows out below
        return _view(val), _view(vfin), _view(other), _view(buckets)

    run.device = host
    return run


class TablePredictor:
    """Prediction engine bound to one table's resolved energy vectors.

    Since the array-backed table refactor, ``EnergyTable`` itself resolves
    into dense energy vectors over ``isa.CLASS_INDEX`` — ``e_pred``
    (Wattchmen-Pred: direct -> scaled -> bucket) and ``e_direct``
    (Wattchmen-Direct: direct hits only, 0 J elsewhere) — cached per table
    version and extended lazily as the index grows.  The predictor is the
    prediction *kernel* over those vectors; mutations through the table's
    dict views invalidate them automatically, and ``invalidate()`` remains
    for out-of-band mutation of table internals.
    """

    def __init__(self, table: EnergyTable, *, fused: bool = False):
        self.table = table
        self._fused_requested = bool(fused)
        self._fused_kernel = None        # built lazily; False = unavailable

    def _vectors(self, n: int):
        """(e_direct, e_pred) resolved for the first ``n`` class ids."""
        return self.table.energy_vectors(n)

    # -- fused (jitted) hot path --------------------------------------------
    def enable_fused(self) -> bool:
        """Opt into the jitted hot path; True when jax is installed.

        Bitwise-identical totals to the plain path (see
        ``_build_fused_kernel``); processes without jax keep the plain
        path, so telemetry shard workers can flip this on untested.  Any
        other failure to build the kernel is raised.
        """
        self._fused_requested = True
        return self._ensure_fused() is not None

    @property
    def fused_device(self):
        """The JAX device the fused kernel runs on; None on the plain path."""
        kern = self._ensure_fused()
        return None if kern is None else kern.device

    def _ensure_fused(self):
        if not self._fused_requested or self._fused_kernel is False:
            return None
        if self._fused_kernel is None:
            if importlib.util.find_spec("jax") is None:
                warnings.warn("fused predict path unavailable (jax is not "
                              "installed); using the plain numpy path",
                              RuntimeWarning, stacklevel=3)
                self._fused_kernel = False
                return None
            self._fused_kernel = _build_fused_kernel()
        return self._fused_kernel

    def warm(self) -> None:
        """Precompute the class->energy vectors for the whole index.

        Worth it on long-lived predictors (the facade, the fleet monitor);
        one-shot callers stay lazy and only resolve the classes they see.
        """
        self._vectors(len(isa.CLASS_INDEX))

    def invalidate(self) -> None:
        """Drop the resolved vectors after a mutation of the bound table."""
        self.table.invalidate_cache()

    # -- operating points ---------------------------------------------------
    @staticmethod
    def _as_point(op):
        """Normalize to ``(freq_mhz, cap|None)`` or ``None`` (nominal)."""
        if op is None:
            return None
        from repro.dvfs.interp import as_point
        return as_point(op)

    def point_powers(self, operating_point=None):
        """``(p_const, p_static)`` at an operating point (table's own when
        ``None`` — the bitwise legacy path)."""
        p = self._as_point(operating_point)
        if p is None:
            return self.table.p_const, self.table.p_static
        rp = self.table.at(p[0], p[1])
        return rp.p_const, rp.p_static

    # -- the kernel ---------------------------------------------------------
    def _predict_rows(self, counts_list: Sequence[OpCounts],
                      durations: Sequence[float],
                      counters_list: Sequence[Optional[Mapping[str, float]]],
                      mode: str, point=None) -> List[Prediction]:
        """One vectorized pass over a stacked counts matrix.

        Every public prediction path funnels through here — a single
        ``predict`` is a 1-row batch — so batched and per-program totals
        come from literally the same float operations (bitwise equal).

        ``point`` (a normalized ``(freq_mhz, cap|None)``) swaps the energy
        vectors and powers for the family-resolved ones (``EnergyTable.at``);
        ``None`` is the nominal anchor — the unchanged legacy expressions.
        """
        n_jobs = len(counts_list)
        n = len(isa.CLASS_INDEX)
        direct_mode = mode == "direct"
        c_mat = counts_matrix(counts_list, n)
        c_mat[:, _COUNTER_IDS] = 0.0          # memory priced from counters
        if point is None:
            e_direct, e_pred = self._vectors(n)
            p_const, p_static = self.table.p_const, self.table.p_static
        else:
            rp = self.table.at(point[0], point[1])
            e_direct, e_pred = rp.vectors(n)
            p_const, p_static = rp.p_const, rp.p_static

        # memory counters: profiled when given, static traffic model else
        mem = np.empty((n_jobs, len(_COUNTER_ITEMS)))
        need_default = [i for i, c in enumerate(counters_list) if c is None]
        if need_default:
            f = _DEFAULT_HBM_BOUNDARY_FRAC
            br = np.asarray([counts_list[i].boundary_read_bytes
                             for i in need_default])
            bw = np.asarray([counts_list[i].boundary_write_bytes
                             for i in need_default])
            leak = np.asarray([counts_list[i].fused_bytes
                               for i in need_default]) * _DEFAULT_FUSED_LEAK
            mem[need_default, 0] = br * f + 0.5 * leak
            mem[need_default, 1] = bw * f + 0.5 * leak
            mem[need_default, 2] = br * (1 - f)
            mem[need_default, 3] = bw * (1 - f)
        given = [i for i, c in enumerate(counters_list) if c is not None]
        if given:
            # one fancy assignment beats n_jobs*4 scalar ndarray stores on
            # the batched-window ingestion path
            mem[given] = [[counters_list[i].get(key, 0.0)
                           for key, _ in _COUNTER_ITEMS] for i in given]

        kern = self._ensure_fused() if n_jobs >= _FUSED_MIN_JOBS else None
        if kern is not None:
            codes = isa.CLASS_INDEX.bucket_codes(n)
            val, val_fin, other, bucket_j = kern(
                c_mat, e_direct, e_pred, codes, mem, direct_mode,
                len(isa.BUCKET_ORDER))
            # np.sum over the same float64 values in the same layout runs
            # the identical pairwise reduction the plain path runs below —
            # and the mode's own sum is reused for the cover/direct twin
            # whose plain-path floats are expression-for-expression the
            # same (``c_mat * e`` appears twice below), so everything the
            # plain path derives stays bitwise while one full product +
            # one full reduction disappear
            dyn = np.sum(val, axis=1)
            osum = np.sum(other, axis=1)
            if direct_mode:
                direct, cover = dyn.copy(), osum
            else:
                cover, direct = dyn.copy(), osum
        else:
            bucket_j = None
            val = c_mat * (e_direct if direct_mode else e_pred)
            val_fin = val            # counter columns land in place below
            dyn = val.sum(axis=1)
            cover = (c_mat * e_pred).sum(axis=1)  # pred-mode energy, all work
            direct = (c_mat * e_direct).sum(axis=1)  # ... direct hits only

        for j, (_, cls) in enumerate(_COUNTER_ITEMS):
            ci = int(_COUNTER_IDS[j])
            units = mem[:, j]
            v = units * (e_direct[ci] if direct_mode else e_pred[ci])
            if bucket_j is None:
                val[:, ci] += v  # the fused kernel already folded these in
            dyn += v
            cover += units * e_pred[ci]
            direct += units * e_direct[ci]

        dur = np.asarray(durations, dtype=float)
        const = p_const * dur
        static = p_static * dur
        total = const + static + dyn
        coverage = np.ones(n_jobs)
        pos = cover > 0
        coverage[pos] = direct[pos] / cover[pos]

        # copy each row out of the batch matrix so a retained Prediction
        # doesn't pin the whole (n_jobs x n_classes) array via a view
        if bucket_j is None:
            return [Prediction(total[i], const[i], static[i], dyn[i],
                               coverage=coverage[i], duration_s=dur[i],
                               class_vec=val_fin[i].copy())
                    for i in range(n_jobs)]
        # bucket rows stay views: the whole bucket matrix is n_buckets
        # floats per job, cheaper pinned than copied
        return [Prediction(total[i], const[i], static[i], dyn[i],
                           coverage=coverage[i], duration_s=dur[i],
                           class_vec=val_fin[i].copy(),
                           bucket_vec=bucket_j[i])
                for i in range(n_jobs)]

    # -- public surface -----------------------------------------------------
    def predict(self, counts: OpCounts, duration_s: float,
                counters: Optional[Mapping[str, float]] = None,
                mode: str = "pred", operating_point=None) -> Prediction:
        return self._predict_rows([counts], [duration_s], [counters], mode,
                                  self._as_point(operating_point))[0]

    def predict_batch(self, counts_list: Sequence[OpCounts],
                      durations: Sequence[float],
                      counters_list: Optional[Sequence[
                          Optional[Mapping[str, float]]]] = None,
                      mode: Union[str, Sequence[str]] = "pred",
                      operating_point=None) -> List[Prediction]:
        """Batched prediction: one matrix pass instead of N table walks.

        ``mode`` may be a single string or a per-job sequence; the same goes
        for ``operating_point`` (an ``OperatingPoint``/tuple/frequency, or a
        per-job sequence of them).  Mixed batches are split into one pass
        per distinct (mode, point) pair, order preserved.
        """
        n_jobs = len(counts_list)
        if counters_list is None:
            counters_list = [None] * n_jobs
        if _is_point_sequence(operating_point):
            pts = [self._as_point(p) for p in operating_point]
        else:
            pts = [self._as_point(operating_point)] * n_jobs
        modes = [mode] * n_jobs if isinstance(mode, str) else list(mode)
        if isinstance(mode, str) and all(p == pts[0] for p in pts):
            return self._predict_rows(counts_list, durations, counters_list,
                                      mode, pts[0])
        out: List[Optional[Prediction]] = [None] * n_jobs
        keys = list(zip(modes, pts))
        for key in dict.fromkeys(keys):          # unique, first-seen order
            ix = [i for i, k in enumerate(keys) if k == key]
            preds = self._predict_rows([counts_list[i] for i in ix],
                                       [durations[i] for i in ix],
                                       [counters_list[i] for i in ix],
                                       key[0], key[1])
            for i, p in zip(ix, preds):
                out[i] = p
        return out  # type: ignore[return-value]


def predict(table: EnergyTable, counts: OpCounts, duration_s: float,
            counters: Optional[Mapping[str, float]] = None,
            mode: str = "pred") -> Prediction:
    """Predict energy for a profiled application run.

    ``mode``: "direct" = Wattchmen-Direct, "pred" = Wattchmen-Pred (§3.4).
    ``counters``: profiled memory counters; fall back to the static traffic
    model when absent (e.g. predicting from a dry-run compile).

    One-shot convenience over ``TablePredictor``; hold a ``TablePredictor``
    (or the ``repro.api.EnergyModel`` facade, which owns one) when predicting
    for many workloads against the same table.
    """
    return TablePredictor(table).predict(counts, duration_s,
                                         counters=counters, mode=mode)


def mape(pairs) -> float:
    """Mean absolute percent error over (predicted, actual) pairs."""
    errs = [abs(p - a) / a for p, a in pairs if a > 0]
    return 100.0 * sum(errs) / max(len(errs), 1)
