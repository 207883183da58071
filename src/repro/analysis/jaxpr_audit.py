"""Layer-1 jaxpr auditor: walk traced epoch programs for contract bugs.

`audit_jaxpr` takes a ClosedJaxpr (from `jax.make_jaxpr` over one of
the real epoch builders — see `analysis.matrix`) and returns findings
for three rules:

* JAX-PSUM-EXCHANGE — psum / psum_scatter ("reduce_scatter") anywhere
  in a deterministic=True trace.  The determinism contract's only
  reductions are ordered gather-sums; sum-reordering collectives have
  no legal site.
* JAX-LOOP-CLOSURE — the shard_map loop-invariant-replicated closure
  hazard (the PR 1 / PR 6 bug class): inside a shard_map region, a
  scan/while const (a value the loop CLOSES OVER, as opposed to its
  carry or scanned xs) that is integer-typed and tainted by
  lax.axis_index.  shard_map treated such closures as replicated on
  the jax of PR 1, so every lane silently ran lane 0's value.  On jax
  0.9 the closed-over form computes per-lane values (a two-lane probe
  of the fori_loop below gives each lane its own slice), but the rule
  stays as the guard, and jax 0.9 hoists a carry the body only
  forwards into such a const — so the legal form is a per-iteration
  scanned input, not a forwarded carry.
* JAX-NONDET-PRIM — other unordered cross-lane reductions (pmax/pmin)
  in a deterministic=True trace.

Taint analysis: `axis_index` outputs seed the taint set; taint
propagates through every equation (any tainted input taints all
outputs) and flows structurally into sub-jaxprs (pjit bodies, scan
carries/xs, cond branches), with loop carries iterated to a fixed
point.  Two deliberate scope cuts, both load-bearing for a
zero-false-positive clean tree:

* only INTEGER-dtype consts are flagged — the hazard class is
  index/offset values (visit perms, slice offsets); float data tiles
  gathered with tainted indices legitimately appear as inner-loop
  consts in the bucket recursion (`sdca.bucket_solve` closes over its
  Gram matrix) and are not scheduling state;
* `pallas_call` bodies are opaque (taint crosses them input->output
  but the walker does not descend): Mosaic kernels have their own
  semantics and no shard_map closures.
"""
from __future__ import annotations

from typing import Any, Optional

from . import config, rules
from .rules import Finding

__all__ = ["audit_jaxpr"]


def _summ(eqn) -> str:
    """file:line anchor for an eqn, best-effort."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    # audit: except-ok best-effort anchor; empty string is the fallback
    except Exception:                     # pragma: no cover - jax-version
        return ""


def _is_int(var) -> bool:
    import jax.numpy as jnp
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    if dtype is None:
        return False
    try:
        return bool(jnp.issubdtype(dtype, jnp.integer))
    # audit: except-ok extension dtypes simply aren't ints
    except Exception:                     # pragma: no cover - ext dtypes
        return False


def _sub_jaxprs(eqn) -> list[tuple[str, Any]]:
    """(param-name, Jaxpr-or-ClosedJaxpr) pairs reachable from eqn."""
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for j in vals:
            if hasattr(j, "eqns") or hasattr(j, "jaxpr"):
                out.append((k, j))
    return out


def _open(j):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _literal_cls():
    try:
        from jax._src.core import Literal
    except ImportError:                   # pragma: no cover - jax-version
        from jax.core import Literal
    return Literal


class _Walker:
    def __init__(self, deterministic: bool, case: str):
        self.deterministic = deterministic
        self.case = case
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()    # dedupe across fixpoint passes

    def _emit(self, rule: str, eqn, message: str) -> None:
        where = _summ(eqn)
        key = (rule, where, message)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(rule, message, where=where,
                                     case=self.case))

    # -- taint plumbing ----------------------------------------------------

    def _run_body(self, j, in_taint: list[bool], in_shard: bool,
                  ) -> list[bool]:
        """Walk one (Closed)Jaxpr body; returns outvar taint flags."""
        jaxpr = _open(j)
        tainted: set = set()
        for var, t in zip(jaxpr.invars, in_taint):
            if t:
                tainted.add(var)
        return self._walk(jaxpr, tainted, in_shard)

    def _loop_fixpoint(self, body, n_consts: int, n_carry: int,
                       in_taint: list[bool], in_shard: bool,
                       ) -> list[bool]:
        """Iterate a scan/while body until carry taint stabilizes."""
        carry = list(in_taint[n_consts:n_consts + n_carry])
        for _ in range(max(n_carry, 1) + 1):
            flags = (in_taint[:n_consts] + carry
                     + in_taint[n_consts + n_carry:])
            out = self._run_body(body, flags, in_shard)
            new_carry = [a or b for a, b in zip(carry, out[:n_carry])]
            if new_carry == carry:
                break
            carry = new_carry
        return out

    # -- the walk ----------------------------------------------------------

    def _walk(self, jaxpr, tainted: set, in_shard: bool) -> list[bool]:
        Literal = _literal_cls()

        def tin(eqn) -> list[bool]:
            return [not isinstance(v, Literal) and v in tainted
                    for v in eqn.invars]

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            flags = tin(eqn)
            out_taint = any(flags)

            if name == "axis_index":
                out_taint = True
            elif name in config.PSUM_PRIMS:
                if self.deterministic:
                    self._emit(
                        rules.JAX_PSUM_EXCHANGE, eqn,
                        f"sum-reordering collective '{name}' in a "
                        f"deterministic=True trace; the contract "
                        f"requires all-gather + ordered jnp.sum")
            elif name in config.NONDET_PRIMS:
                if self.deterministic:
                    self._emit(
                        rules.JAX_NONDET_PRIM, eqn,
                        f"unordered cross-lane reduction '{name}' in "
                        f"a deterministic=True trace")

            if name == "pallas_call":
                pass                       # opaque: propagate, no descent
            elif name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                if in_shard:
                    self._check_consts(eqn, flags[:nc], "scan")
                out = self._loop_fixpoint(eqn.params["jaxpr"], nc, ncar,
                                          flags, in_shard)
                out_taint = None           # per-outvar flags below
                outs = out
            elif name == "while":
                bn = eqn.params["body_nconsts"]
                cn = eqn.params["cond_nconsts"]
                if in_shard:
                    self._check_consts(
                        eqn, flags[cn:cn + bn], "while/fori_loop",
                        offset=cn)
                ncar = len(flags) - cn - bn
                outs = self._loop_fixpoint(
                    eqn.params["body_jaxpr"], bn, ncar,
                    flags[cn:], in_shard)
                self._run_body(eqn.params["cond_jaxpr"],
                               flags[:cn] + outs, in_shard)
                out_taint = None
            elif name == "cond":
                outs = [False] * len(eqn.outvars)
                for br in eqn.params["branches"]:
                    o = self._run_body(br, flags[1:], in_shard)
                    outs = [a or b for a, b in zip(outs, o)]
                out_taint = None
            else:
                # generic descent: pjit / remat / custom_* / anything
                # else carrying sub-jaxprs.  shard_map marks the region
                # the closure rule applies to.
                descend_shard = in_shard or name == "shard_map"
                outs = None
                for _, j in _sub_jaxprs(eqn):
                    body = _open(j)
                    if len(body.invars) == len(flags):
                        o = self._run_body(j, flags, descend_shard)
                    else:
                        # arity mismatch (custom_jvp residuals etc.):
                        # conservatively taint every body input if any
                        # eqn input is tainted
                        o = self._run_body(
                            j, [any(flags)] * len(body.invars),
                            descend_shard)
                    if len(o) == len(eqn.outvars):
                        outs = ([a or b for a, b in zip(outs, o)]
                                if outs is not None else o)
                if outs is not None:
                    out_taint = None

            if out_taint is None:
                for var, t in zip(eqn.outvars, outs):
                    if t:
                        tainted.add(var)
            elif out_taint:
                for var in eqn.outvars:
                    tainted.add(var)

        return [not isinstance(v, Literal) and v in tainted
                for v in jaxpr.outvars]

    def _check_consts(self, eqn, const_flags: list[bool], kind: str,
                      offset: int = 0) -> None:
        for i, t in enumerate(const_flags):
            var = eqn.invars[offset + i]
            if t and _is_int(var):
                self._emit(
                    rules.JAX_LOOP_CLOSURE, eqn,
                    f"{kind} inside shard_map closes over a "
                    f"loop-invariant integer value derived from "
                    f"axis_index (const #{i}, "
                    f"{getattr(var, 'aval', '?')}); pass it as a "
                    f"scanned xs input (a carry the body only forwards "
                    f"is hoisted into a const too) — shard_map may "
                    f"replicate closed-over values across lanes")


def audit_jaxpr(closed, *, deterministic: bool, case: str = "",
                only: Optional[set] = None) -> list[Finding]:
    """Audit one ClosedJaxpr; returns rule findings (empty = clean).

    ``deterministic`` states whether the traced program ran under the
    determinism contract (enables the reduction rules; the closure
    rule applies either way).  ``only`` optionally restricts to a
    subset of rule IDs.
    """
    w = _Walker(deterministic, case)
    w._walk(closed.jaxpr, set(), in_shard=False)
    found = w.findings
    if only is not None:
        found = [f for f in found if f.rule in only]
    return found
