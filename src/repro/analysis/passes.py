"""Jaxpr/HLO invariant passes over traced and lowered programs.

Grown out of ``utils/hlo.py``'s single-purpose helpers: the DCE-aware
liveness walk (head-fusion memory claims) and the collective-bytes
scanner (roofline) now live here as reusable passes, joined by two new
ones:

``dtype_drift``
    walks a jaxpr for live ``convert_element_type`` equations lifting a
    narrow dtype to a wide one above an element-count threshold — the
    regression it exists for is the bf16 compressed teacher cache being
    silently upcast to f32 somewhere in the KD program, doubling the
    O(server-set) cache residency.  Small per-tile upcasts (the flash
    kernel's f32 accumulators, per-batch boundary casts) sit below the
    threshold and stay legal.
``donation_audit``
    compares donations *requested* against donations *honored*: an
    honored donation is an ``input_output_alias`` entry in the compiled
    HLO module; a donated-but-copied arg (dtype changed, shape changed,
    output mismatch) has none, and XLA quietly keeps both buffers — the
    engine's donate-through-scan memory story depends on these actually
    aliasing.

``utils.hlo`` re-exports the migrated names with a DeprecationWarning.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Var

# dtype -> bytes per element (HLO + StableHLO spellings)
_DTYPE_BYTES = {
    "pred": 1, "i1": 1,
    "s8": 1, "u8": 1, "i8": 1, "ui8": 1,
    "s16": 2, "u16": 2, "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "i32": 4, "ui32": 4, "f32": 4,
    "s64": 8, "u64": 8, "i64": 8, "ui64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

# e.g.  %all-reduce.5 = f32[8,1024]{1,0} all-reduce(...)
_HLO_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9_]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|collective-broadcast)"
)
# tuple-typed collectives:  = (f32[..], f32[..]) all-reduce(
_HLO_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|collective-broadcast)"
)
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype)
    if bpe is None:
        return 0
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * bpe


@dataclass
class CollectiveStats:
    """Bytes moved by each collective kind in one compiled module."""
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())

    def add(self, kind: str, nbytes: int) -> None:
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + nbytes
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + 1

    def summary(self) -> str:
        parts = [
            f"{k}: {self.count_by_kind[k]} ops, "
            f"{self.bytes_by_kind[k] / 1e9:.4f} GB"
            for k in sorted(self.bytes_by_kind)
        ]
        return "; ".join(parts) if parts else "(no collectives)"


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in HLO text.

    We use the *result* shape: for all-gather that is the gathered size,
    for all-reduce the reduced tensor, for reduce-scatter the scattered
    shard — a consistent, slightly conservative proxy for wire bytes per
    chip.  Works on HLO (``compiled.as_text()``) and StableHLO
    (``lowered.as_text()``) alike.
    """
    stats = CollectiveStats()
    seen_spans = set()
    for m in _HLO_OP_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        stats.add(kind, _shape_bytes(dtype, dims))
        seen_spans.add((m.start(3), m.end(3)))
    for m in _HLO_TUPLE_RE.finditer(hlo_text):
        if (m.start(2), m.end(2)) in seen_spans:
            continue
        kind = m.group(2)
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(m.group(1)))
        stats.add(kind, nbytes)
    return stats


def duplicate_fusion_count(hlo_text: str) -> int:
    """Rough remat indicator: number of non-unique fusion bodies."""
    names = re.findall(r"^\s*%?(fused_[a-z0-9_.]+)\s*\(", hlo_text, re.M)
    return len(names) - len(set(names))


# ---------------------------------------------------------------------
# jaxpr liveness analysis (memory-bound claims)
# ---------------------------------------------------------------------
def _sub_jaxprs(val):
    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def _live_walk(jaxpr, visit) -> None:
    """Reverse liveness pass: call ``visit(eqn)`` for every LIVE eqn,
    recursively through scan/cond/pjit/custom-vjp sub-jaxprs.

    Dead equations — e.g. the symbolic-zero cotangent jax instantiates
    for a frozen (non-differentiated) operand, which XLA removes — are
    skipped, so visited equations reflect what a compiled program
    actually executes.
    """
    live = {v for v in jaxpr.outvars if isinstance(v, Var)}
    for eqn in reversed(jaxpr.eqns):
        if not any(isinstance(v, Var) and v in live for v in eqn.outvars):
            continue                      # dead: no consumer downstream
        for v in eqn.invars:
            if isinstance(v, Var):
                live.add(v)
        visit(eqn)
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _live_walk(sub, visit)


def live_intermediates(jaxpr) -> list:
    """Every live intermediate as ``(shape, dtype)`` tuples (with
    duplicates — one entry per eqn output that owns the buffer)."""
    out = []

    def visit(eqn):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append((tuple(aval.shape),
                            np.dtype(getattr(aval, "dtype", np.float32))))

    _live_walk(jaxpr, visit)
    return out


def live_intermediate_shapes(jaxpr) -> set:
    """Every LIVE intermediate (eqn output) shape in a jaxpr.

    The flash-KD benches and tests use this to assert the head-fused
    path never materializes the ``(B, V)`` student logit row (live
    student memory is O(B·tile)).
    """
    return {shape for shape, _ in live_intermediates(jaxpr)}


def max_live_intermediate_bytes(jaxpr) -> int:
    """Size of the single largest live intermediate buffer.

    A conservative lower bound on peak memory and the right gate for
    "never materializes X"-style claims: if the bound is O(tile), no
    O(B·V) buffer exists anywhere in the live program.
    """
    best = 0
    for shape, dtype in live_intermediates(jaxpr):
        n = 1
        for d in shape:
            n *= int(d)
        best = max(best, n * dtype.itemsize)
    return best


# ---------------------------------------------------------------------
# dtype drift (bf16 cache upcast to f32)
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class DtypeDrift:
    """One wide upcast: a live convert_element_type above threshold."""
    shape: tuple
    src: str
    dst: str

    @property
    def elements(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n


def dtype_drift(jaxpr, src="bfloat16", dst="float32",
                min_elements: int = 1 << 20) -> list:
    """Live ``convert_element_type`` eqns lifting ``src``→``dst`` whose
    output holds at least ``min_elements`` elements.

    The default threshold (1 Mi elements) is far above any per-tile or
    per-batch boundary cast and far below a full compressed teacher
    cache, so hits mean exactly the regression the pass exists for: a
    cache-width tensor silently living at double width.
    """
    src_dt, dst_dt = np.dtype(src), np.dtype(dst)
    hits = []

    def visit(eqn):
        if eqn.primitive.name != "convert_element_type":
            return
        in_aval = getattr(eqn.invars[0], "aval", None)
        out_aval = getattr(eqn.outvars[0], "aval", None)
        if in_aval is None or out_aval is None:
            return
        if (np.dtype(getattr(in_aval, "dtype", None)) != src_dt
                or np.dtype(getattr(out_aval, "dtype", None)) != dst_dt):
            return
        drift = DtypeDrift(tuple(out_aval.shape), str(src_dt), str(dst_dt))
        if drift.elements >= min_elements:
            hits.append(drift)

    _live_walk(jaxpr, visit)
    return hits


# ---------------------------------------------------------------------
# donation audit (donated args XLA copied anyway)
# ---------------------------------------------------------------------
_ALIAS_RE = re.compile(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:")


@dataclass(frozen=True)
class DonationReport:
    """Requested vs honored donations for one compiled program.

    ``requested`` counts flat donated inputs (from ``donate_argnums``);
    ``honored`` counts the compiled module's ``input_output_alias``
    entries, i.e. donated buffers the executable really reuses for an
    output.  The lowered MLIR is no evidence: it marks every donated
    parameter (``jax.buffer_donor``) whether or not XLA can alias it.
    ``requested > honored`` means XLA copies a buffer the caller believes
    it reuses in place.
    """
    requested: int
    honored: int

    @property
    def copied(self) -> int:
        return max(0, self.requested - self.honored)

    @property
    def ok(self) -> bool:
        return self.copied == 0


def donation_audit(fn_or_lowered, *args, **kwargs) -> DonationReport:
    """Audit a jitted function's (or prebuilt Lowered's) donations.

    Pass either ``jax.jit(f, donate_argnums=...)`` plus example args —
    the audit lowers and compiles it — or an already-lowered object.
    """
    import jax
    lowered = fn_or_lowered
    if not hasattr(lowered, "as_text"):
        lowered = fn_or_lowered.lower(*args, **kwargs)
    flat, _ = jax.tree.flatten(lowered.args_info)
    requested = sum(bool(a.donated) for a in flat)
    m = _ALIAS_RE.search(lowered.compile().as_text())
    honored = len(_ALIAS_ENTRY_RE.findall(m.group(1))) if m else 0
    return DonationReport(requested=requested, honored=honored)
