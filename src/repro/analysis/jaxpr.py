"""Precision-provenance audit over the jitted hot paths (jaxpr level).

ROADMAP item 1 wants the proxy GEMMs, Gram rerank, and support SpMM in
block-scaled int8/fp8 — but "measure the trade, don't leap" needs a
starting line: *where exactly does the fused query pipeline widen a
narrow dtype today, and from which operand did the narrow value come?*
AST checks cannot see this — the upcasts happen inside jitted functions,
sometimes implicitly (``dot_general``/``add`` type promotion), sometimes
behind a gather chain.  So this module traces the registered hot paths
to closed jaxprs with tiny example inputs and walks the equations:

* every *narrow* input (int8/uint8/int16/uint16/float16/bfloat16) seeds
  a provenance record ``(origin argument, primitive chain)``;
* provenance flows through equations whose outputs stay narrow
  (``gather``, ``slice``, ``reshape`` …), extending the chain;
* an equation whose output is *wider* than a narrow input — a larger
  itemsize, or an int→float conversion — is a **widening**: reported
  with the primitive (``convert_element_type``, ``dot_general``, …), the
  dtypes, the provenance chain back to the origin argument, and the
  user-code line from the eqn's source info.

Sub-jaxprs (``jit``/``scan``/``cond``/custom-call wrappers) are walked
recursively so provenance crosses inlined jit boundaries; anything that
cannot be mapped through (e.g. a ``pallas_call``'s ref-typed kernel
jaxpr) falls back to the boundary rule — a narrow operand entering an
opaque equation that emits wider output is itself the widening.

Findings wear check name ``precision-widening`` and feed the same
reasoned-suppression machinery as every other reprolint check; the
committed ``PRECISION_audit.json`` is their baseline (every entry's
``reason`` is mandatory) *and* the measured inventory ROADMAP item 1
starts from.  Symbols are keyed on (hot path, origin, primitive, dtype
pair) — never line numbers — so the audit survives unrelated edits.

The deliberate pattern this audit blesses today: int8 rating *storage*
gathered narrow and cast to f32 *in-register* right before exact Gram
arithmetic (exact for MovieLens-style integer ratings; the narrow gather
is the bandwidth win).  The audit exists so the day a widening appears
*before* the gather — or a new one sneaks in — the gate fails loudly.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding

AUDIT_SCHEMA = "repro.analysis.precision/v1"
CHECK = "precision-widening"

#: dtypes whose values we track as "narrow" sources.  bool is excluded
#: (masks widen by design and carry one bit of information); int32/int64
#: index math is excluded by construction (indices are never narrow).
NARROW_DTYPES = ("int8", "uint8", "int16", "uint16", "float16", "bfloat16")


@dataclasses.dataclass
class Widening:
    hot_path: str            # registry name, e.g. "index.clustered._fused_rerank_block"
    path: str                # repo-relative source file of the hot path
    origin: str              # argument the narrow value came from
    prim: str                # primitive that widened it
    from_dtype: str
    to_dtype: str
    provenance: Tuple[str, ...]   # primitive chain origin → widening site
    line: int = 0            # user-code line (informational, not keyed)
    file: str = ""

    @property
    def symbol(self) -> str:
        return (f"{self.hot_path}:{self.origin}:{self.prim}:"
                f"{self.from_dtype}->{self.to_dtype}")

    def to_json(self) -> dict:
        return {
            "hot_path": self.hot_path, "path": self.path,
            "symbol": self.symbol, "origin": self.origin,
            "prim": self.prim, "from_dtype": self.from_dtype,
            "to_dtype": self.to_dtype,
            "provenance": list(self.provenance),
            "line": self.line, "file": self.file,
        }


# -- the jaxpr walk ----------------------------------------------------------

class _Prov:
    __slots__ = ("origin", "dtype", "chain")

    def __init__(self, origin: str, dtype: str, chain: Tuple[str, ...]):
        self.origin, self.dtype, self.chain = origin, dtype, chain


def _dtype_of(v) -> Optional[str]:
    try:
        return str(v.aval.dtype)
    except Exception:  # reprolint: disable=silent-fallback -- a missing dtype (ref/token/abstract avals) IS the answer: the var is untrackable, caller skips it
        return None


def _is_narrow(dt: Optional[str]) -> bool:
    return dt in NARROW_DTYPES


def _itemsize(dt: str) -> int:
    return np.dtype(dt).itemsize


def _widens(from_dt: str, to_dt: str) -> bool:
    """Larger itemsize, or int→float at any size, counts as widening."""
    try:
        f, t = np.dtype(from_dt), np.dtype(to_dt)
    except TypeError:
        return False
    if t.kind == "b":
        return False                      # comparisons are not upcasts
    if t.itemsize > f.itemsize:
        return True
    return f.kind in "iu" and t.kind == "f"


def _eqn_line(eqn) -> Tuple[str, int]:
    """First user frame inside the repo for an eqn, best effort."""
    try:
        from jax._src import source_info_util
        for fr in source_info_util.user_frames(eqn.source_info):
            fname = str(fr.file_name).replace("\\", "/")
            if "/repro/" in fname:
                short = "src/repro/" + fname.split("/repro/", 1)[1]
                return short, int(fr.start_line
                                  if hasattr(fr, "start_line")
                                  else fr.line_num)
    except Exception:  # reprolint: disable=silent-fallback -- line attribution is cosmetic (findings are keyed on symbols, never lines); a finding without a line still gates
        pass
    return "", 0


_SUBJAXPR_1TO1 = {"jit", "closed_call", "core_call", "remat", "remat2",
                  "checkpoint", "custom_jvp_call", "custom_vjp_call",
                  "custom_jvp_call_jaxpr", "scan"}


def _sub_jaxprs(eqn):
    """(closed_or_raw_jaxpr, invar_offset) candidates for recursion."""
    import jax.extend.core as jcore
    ClosedJaxpr = jcore.ClosedJaxpr
    name = eqn.primitive.name
    out = []
    if name == "cond":
        for br in eqn.params.get("branches", ()):
            out.append((br, 1))          # invars[0] is the predicate
        return out
    if name not in _SUBJAXPR_1TO1:
        return []
    for key in ("jaxpr", "call_jaxpr"):
        v = eqn.params.get(key)
        if isinstance(v, (ClosedJaxpr, jcore.Jaxpr)):
            out.append((v, 0))
    return out


def _walk_jaxpr(jaxpr, prov: Dict[object, _Prov], hot_path: str,
                path: str, out: List[Widening],
                seen: Dict[str, Widening]) -> None:
    import jax.extend.core as jcore
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        narrow_ins = []
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                continue
            p = prov.get(v)
            if p is not None:
                narrow_ins.append(p)
        if not narrow_ins:
            continue

        # try to push provenance through sub-jaxprs for finer attribution
        subs = _sub_jaxprs(eqn)
        recursed = False
        for sub, off in subs:
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            invars = list(inner.invars)
            outer = list(eqn.invars)[off:]
            if len(invars) != len(outer):
                continue
            inner_prov: Dict[object, _Prov] = {}
            for iv, ov in zip(invars, outer):
                if isinstance(ov, jcore.Literal):
                    continue
                p = prov.get(ov)
                if p is not None:
                    inner_prov[iv] = p
            if not inner_prov:
                continue
            _walk_jaxpr(inner, inner_prov, hot_path, path, out, seen)
            # propagate narrow provenance across the call boundary
            for inner_ov, outer_ov in zip(inner.outvars, eqn.outvars):
                p = inner_prov.get(inner_ov)
                dt = _dtype_of(outer_ov)
                if p is not None and _is_narrow(dt):
                    prov[outer_ov] = _Prov(p.origin, dt,
                                           p.chain + (prim,))
            recursed = True
        if recursed:
            continue

        # boundary rule: does this eqn widen any narrow input?
        for ov in eqn.outvars:
            dt = _dtype_of(ov)
            if dt is None:
                continue
            if _is_narrow(dt):
                # stays narrow: extend the chain from the first narrow in
                p = narrow_ins[0]
                prov[ov] = _Prov(p.origin, dt, p.chain + (prim,))
                continue
            for p in narrow_ins:
                if not _widens(p.dtype, dt):
                    continue
                w = Widening(
                    hot_path=hot_path, path=path, origin=p.origin,
                    prim=prim, from_dtype=p.dtype, to_dtype=dt,
                    provenance=p.chain + (prim,))
                w.file, w.line = _eqn_line(eqn)
                if w.symbol not in seen:
                    seen[w.symbol] = w
                    out.append(w)
                break


def trace_widenings(fn: Callable, args: Sequence, arg_names: Sequence[str],
                    *, hot_path: str, path: str) -> List[Widening]:
    """Trace ``fn(*args)`` to a closed jaxpr and report every widening of
    a narrow-dtyped argument, with provenance.  ``arg_names`` label the
    positional args (the origin names in the report)."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    prov: Dict[object, _Prov] = {}
    for v, name in zip(closed.jaxpr.invars, arg_names):
        dt = _dtype_of(v)
        if _is_narrow(dt):
            prov[v] = _Prov(name, dt, ())
    for v in closed.jaxpr.constvars:
        dt = _dtype_of(v)
        if _is_narrow(dt):
            prov[v] = _Prov("<const>", dt, ())
    out: List[Widening] = []
    _walk_jaxpr(closed.jaxpr, prov, hot_path, path, out, {})
    return out


# -- hot-path registry -------------------------------------------------------

@dataclasses.dataclass
class HotPath:
    name: str
    path: str                       # repo-relative source file
    build: Callable[[], tuple]      # -> (jit_fn, call, make_args, arg_names)


def _np_ratings(u=8, d=6, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(0, 6, size=(u, d)).astype(np.int8)
    return r


def _common():
    import jax.numpy as jnp
    from repro.core.predict import make_gather_source
    r8 = _np_ratings()
    ratings = jnp.asarray(r8, jnp.float32)
    r_gather = make_gather_source(ratings)           # int8 gather code
    norms = jnp.sqrt(jnp.sum(ratings * ratings, -1))
    counts = jnp.sum(ratings > 0, -1).astype(jnp.float32)
    return r_gather, ratings, norms, counts


def _build_fused_scan_pool():
    import jax.numpy as jnp
    from repro.index import clustered as cl
    fn = cl._fused_scan_pool

    def make_args():
        rng = np.random.default_rng(1)
        proxies = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        q_ids = jnp.asarray([0, 3], jnp.int32)
        return (proxies, q_ids)

    call = functools.partial(fn, m=3)
    return fn, call, make_args, ("proxies", "q_ids")


def _build_fused_scan_restricted():
    import jax.numpy as jnp
    from repro.index import clustered as cl
    fn = cl._fused_scan_restricted

    def make_args():
        rng = np.random.default_rng(2)
        proxies = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        cand_pad = jnp.asarray([1, 2, 4, 6, 8], jnp.int32)
        q_ids = jnp.asarray([0, 3], jnp.int32)
        return (proxies, cand_pad, q_ids)

    call = functools.partial(fn, m=3)
    return fn, call, make_args, ("proxies", "cand_pad", "q_ids")


def _build_fused_rerank_block():
    import jax.numpy as jnp
    from repro.index import clustered as cl
    fn = cl._fused_rerank_block

    def make_args():
        r_gather, ratings, norms, counts = _common()
        q_ids = jnp.asarray([0, 3], jnp.int32)
        shorts = jnp.asarray([[1, 2, 8], [4, 5, 8]], jnp.int32)
        return (r_gather, ratings, norms, counts, q_ids, shorts)

    call = functools.partial(fn, ku=4, k=2, measure="pcc_sig", beta=50.0,
                             use_pallas=False, interpret=False)
    return fn, call, make_args, ("r_gather", "ratings", "norms", "counts",
                                 "q_ids", "shorts")


def _build_fused_rerank_whole():
    import jax.numpy as jnp
    from repro.index import clustered as cl
    fn = cl._fused_rerank_whole

    def make_args():
        r_gather, _, norms, counts = _common()
        q_ids = jnp.asarray([0, 3], jnp.int32)
        shorts = jnp.asarray([[1, 2, 8], [4, 5, 8]], jnp.int32)
        return (r_gather, norms, counts, q_ids, shorts)

    call = functools.partial(fn, k=2, measure="pcc_sig", beta=50.0,
                             use_pallas=False, interpret=False)
    return fn, call, make_args, ("r_gather", "norms", "counts", "q_ids",
                                 "shorts")


def _build_rerank_sparse():
    import jax.numpy as jnp
    from repro.index import clustered as cl
    fn = cl._rerank_sparse

    def make_args():
        r_gather, ratings, norms, counts = _common()
        q_ids = jnp.asarray([0, 3], jnp.int32)
        q_items = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
        q_vals = jnp.asarray([[5.0, 3.0, 0.0], [4.0, 1.0, 2.0]],
                             jnp.float32)
        cand_ids = jnp.asarray([[1, 2, 8], [4, 5, 8]], jnp.int32)
        return (r_gather, norms, counts, q_ids, q_items, q_vals, cand_ids)

    call = functools.partial(fn, k=2, measure="pcc_sig", beta=50.0)
    return fn, call, make_args, ("r_gather", "norms", "counts", "q_ids",
                                 "q_items", "q_vals", "cand_ids")


def _build_rerank_scores_xla():
    import jax.numpy as jnp
    from repro.kernels import rerank as rk
    fn = rk.rerank_scores_xla

    def make_args():
        r_gather, ratings, norms, counts = _common()
        q_vals = ratings[:2]
        cand_rows = r_gather.code[:4]                # int8, as the fused
        return (q_vals, cand_rows, norms[:4], counts[:4])

    call = functools.partial(fn, measure="pcc_sig", beta=50.0)
    return fn, call, make_args, ("q_vals", "cand_rows", "cand_norms",
                                 "cand_counts")


def _build_scan_topm_xla():
    import jax.numpy as jnp
    from repro.kernels import select as sel
    fn = sel.scan_topm_xla

    def make_args():
        rng = np.random.default_rng(3)
        proxies = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        q = proxies[:2]
        q_ids = jnp.asarray([0, 3], jnp.int32)
        return (q, proxies, q_ids)

    call = functools.partial(fn, m=3)
    return fn, call, make_args, ("q", "proxies", "q_ids")


def _build_fused_support_scores():
    import jax.numpy as jnp
    from repro.kernels import support as sup
    fn = sup.fused_support_scores

    def make_args():
        rng = np.random.default_rng(4)
        dev = jnp.asarray(rng.normal(size=(8, 1, 8)), jnp.float32)
        msk = jnp.asarray((rng.random((8, 1, 8)) > 0.5), jnp.float32)
        nb_idx = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        nb_w = jnp.asarray([[0.5, 0.5], [1.0, 0.0]], jnp.float32)
        q_means = jnp.asarray([3.0, 2.5], jnp.float32)
        return (dev, msk, nb_idx, nb_w, q_means)

    call = functools.partial(fn, scale=(1.0, 5.0), bt=4, interpret=True)
    return fn, call, make_args, ("dev", "msk", "nb_idx", "nb_w", "q_means")


def _build_select_rerank_items():
    import jax.numpy as jnp
    from repro.index import item_index as ii
    fn = ii._select_rerank_items

    def make_args():
        r_gather, ratings, _, _ = _common()
        rng = np.random.default_rng(5)
        nb_scores = jnp.asarray(rng.random((8, 2)), jnp.float32)
        nb_idx = jnp.asarray(rng.integers(0, 8, (8, 2)), jnp.int32)
        means = jnp.asarray(rng.uniform(1, 5, 8), jnp.float32)
        q_ids = jnp.asarray([0, 3], jnp.int32)
        scores = jnp.asarray(rng.uniform(1, 5, (2, 8)), jnp.float32)
        return (ratings, r_gather, nb_scores, nb_idx, means, q_ids, scores,
                jnp.int32(3))

    call = functools.partial(fn, n=2, shortlist=4, item_block=4)
    return fn, call, make_args, ("ratings", "gather_code", "nb_scores",
                                 "nb_idx", "means", "q_ids", "scores", "m")


#: The fused query pipeline + its twins: the surfaces ROADMAP item 1 will
#: quantize, in execution order.  Statics are bound to the XLA twins
#: (use_pallas=False / interpret=True) so the audit traces on any host.
HOT_PATHS: Tuple[HotPath, ...] = (
    HotPath("index.clustered._fused_scan_pool",
            "src/repro/index/clustered.py", _build_fused_scan_pool),
    HotPath("index.clustered._fused_scan_restricted",
            "src/repro/index/clustered.py", _build_fused_scan_restricted),
    HotPath("index.clustered._fused_rerank_block",
            "src/repro/index/clustered.py", _build_fused_rerank_block),
    HotPath("index.clustered._fused_rerank_whole",
            "src/repro/index/clustered.py", _build_fused_rerank_whole),
    HotPath("index.clustered._rerank_sparse",
            "src/repro/index/clustered.py", _build_rerank_sparse),
    HotPath("kernels.rerank.rerank_scores_xla",
            "src/repro/kernels/rerank.py", _build_rerank_scores_xla),
    HotPath("kernels.select.scan_topm_xla",
            "src/repro/kernels/select.py", _build_scan_topm_xla),
    HotPath("kernels.support.fused_support_scores",
            "src/repro/kernels/support.py", _build_fused_support_scores),
    HotPath("index.item_index._select_rerank_items",
            "src/repro/index/item_index.py", _build_select_rerank_items),
)


def run_precision_audit(hot_paths: Sequence[HotPath] = HOT_PATHS
                        ) -> List[Widening]:
    """Trace every registered hot path; returns all widenings found."""
    out: List[Widening] = []
    for hp in hot_paths:
        fn, call, make_args, arg_names = hp.build()
        out.extend(trace_widenings(call, make_args(), arg_names,
                                   hot_path=hp.name, path=hp.path))
    return out


def widening_findings(widenings: Sequence[Widening]) -> List[Finding]:
    out = []
    for w in widenings:
        out.append(Finding(
            check=CHECK, path=w.path, line=w.line, col=0,
            symbol=w.symbol,
            message=f"{w.hot_path}: {w.origin} ({w.from_dtype}) widened "
                    f"to {w.to_dtype} by {w.prim} "
                    f"(provenance {' -> '.join(w.provenance)}) — either "
                    f"intentional (baseline it in PRECISION_audit.json "
                    f"with a reason) or a bandwidth regression"))
    return out


# -- the committed audit file ------------------------------------------------

def load_audit(path) -> Dict[Tuple[str, str, str], str]:
    """PRECISION_audit.json → baseline map {(check, path, symbol): reason}.
    Like reprolint_baseline.json, a reasonless entry is a hard error."""
    p = Path(path)
    if not p.exists():
        return {}
    data = json.loads(p.read_text())
    if data.get("schema") != AUDIT_SCHEMA:
        raise ValueError(f"unsupported precision-audit schema in {path}: "
                         f"{data.get('schema')!r}")
    out = {}
    for e in data.get("entries", []):
        reason = e.get("reason", "").strip()
        if not reason:
            raise ValueError(
                f"precision-audit entry without a reason in {path}: "
                f"{e.get('symbol')!r} — every accepted widening must say "
                f"why it is exact/intentional")
        out[(CHECK, e["path"], e["symbol"])] = reason
    return out


def write_audit(path, widenings: Sequence[Widening],
                reasons: Optional[Dict[str, str]] = None) -> int:
    """Write the audit file from a fresh trace, preserving ``reasons``
    (symbol → reason, e.g. from the previous audit) and stamping
    ``TODO`` on new entries for the operator to fill in."""
    reasons = reasons or {}
    entries = []
    for w in sorted(widenings, key=lambda w: (w.path, w.symbol)):
        e = w.to_json()
        e["reason"] = reasons.get(w.symbol, "TODO: justify or eliminate")
        entries.append(e)
    Path(path).write_text(json.dumps(
        {"schema": AUDIT_SCHEMA, "entries": entries}, indent=2) + "\n")
    return len(entries)
