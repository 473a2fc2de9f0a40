"""Jaxpr coverage audit for the quantized-GEMM policy (paper eq. 8a).

The policy's guarantee is *per-operation* (Stochastic Rounding 2.0; On
Stochastic Rounding with Few Random Bits — PAPERS.md): every weight-bearing
GEMM must run through the rounded Pallas kernels, because any full-precision
hole re-admits the deterministic-rounding stagnation of paper §3.  This
module makes that auditable: it walks a traced fwd(+bwd) jaxpr and reports
which *parameter leaves* reach a full-precision ``dot_general``.

Mechanism — taint propagation with a quantization barrier:

* every parameter leaf starts tainted with its own tree path;
* taint flows through every equation (elementwise ops, reshapes, gathers,
  control flow: scan/while/cond/pjit/custom-vjp/shard_map are descended
  into, scan/while carried to a fixpoint);
* ``pallas_call`` outputs are **untainted** — the quantized kernels are the
  sanctioned sink for weights, so anything downstream of one is treated as
  an activation;
* a ``dot_general`` *records* the union of its operands' taints.

A dot_general with an empty taint set is an activation-activation
contraction (attention logits/probs, SSD/wkv state recurrences) — outside
the weight-GEMM contract by construction.  A non-empty taint set names the
param leaves that reached a full-precision GEMM; the audit passes when all
of them are on the intentional-fp32 allowlist below.

``ALLOWED_FP32_LEAVES`` (see EXPERIMENTS.md §Quantized GEMM path for the
rationale of each entry): norm scales, embeddings (enter compute through a
gather into the residual stream), the RWKV data-dependent decay MLP and
per-head bonus (their outputs feed exp(); an 8-bit grid would collapse
whole heads), RWKV token-shift lerp weights, and the SSM depthwise-conv /
decay / skip scalars — all of which touch dot_generals only through
activation operands, never as a contracted weight.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, FrozenSet, List, Optional, Sequence

import jax
from jax.extend import core

EMPTY: FrozenSet[str] = frozenset()

# Entries are path *suffixes* ("/"-separated tree-path components matched
# from the right): bare names like "norm1" exempt that leaf anywhere, while
# qualified entries like "rwkv/u" exempt the leaf only under its module —
# so a future weight that happens to reuse a generic name ("u", "D") in
# another module is NOT silently exempted from the guard.
ALLOWED_FP32_LEAVES: FrozenSet[str] = frozenset({
    # norm scales/biases: taint GEMM operands through normalized activations
    "norm", "norm1", "norm2", "norm_x", "final_norm", "enc_norm",
    "q_norm", "kv_norm", "ln_out",
    # embeddings: enter via gather into the residual stream (the tied
    # lm-head GEMM itself is guarded directly by
    # test_quant_coverage.test_tied_embedding_logits_site_quantized)
    "embed",
    # RWKV: data-dependent decay MLP + first-token bonus + shift lerps
    "rwkv/decay_w0", "rwkv/decay_a", "rwkv/decay_b", "rwkv/u",
    "rwkv/mu_r", "rwkv/mu_k", "rwkv/mu_v", "rwkv/mu_w", "rwkv/mu_g",
    "rwkv/cm_mu_k", "rwkv/cm_mu_r",
    # SSM: depthwise conv, decay/skip/dt scalars (elementwise by design)
    "ssm/conv_w", "ssm/conv_b", "ssm/A_log", "ssm/D", "ssm/dt_bias",
})


def _is_allowed(path: str, allowed: FrozenSet[str]) -> bool:
    parts = path.split("/")
    for entry in allowed:
        ep = entry.split("/")
        if parts[-len(ep):] == ep:
            return True
    return False


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """Outcome of one jaxpr coverage audit."""
    reached: FrozenSet[str]       # param-leaf paths reaching a dot_general
    n_dot_general: int            # distinct dot_general equations seen
    n_quantized_calls: int        # distinct pallas_call equations seen
    n_act_dot_general: int = 0    # dot_generals with NO tainted operand —
    #   activation-activation contractions (attention logits/AV, state
    #   recurrences).  The attention-site policies exist to retire these:
    #   with `binary8-paper-attn` active, the flash prefill's QK^T/AV
    #   contractions move inside a pallas_call and this count drops
    #   (tests/test_quant_coverage.py asserts the delta).

    def offenders(self, allowed: FrozenSet[str] = ALLOWED_FP32_LEAVES
                  ) -> FrozenSet[str]:
        """Param paths NOT matched by an fp32-allowlist suffix."""
        return frozenset(p for p in self.reached
                         if not _is_allowed(p, allowed))

    @property
    def ok(self) -> bool:
        return not self.offenders()


def _key_str(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _leaf_paths(tree) -> List[str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(_key_str(k) for k in path) for path, _ in flat]


def _inner_jaxpr(obj):
    """ClosedJaxpr | Jaxpr -> Jaxpr (None for anything else)."""
    if isinstance(obj, core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, core.Jaxpr):
        return obj
    return None


class _Walker:
    """Taint propagation over a jaxpr (see module docstring)."""

    def __init__(self):
        self.reached: set = set()
        self._dot_eqns: set = set()      # by id(): fixpoint reruns must not
        self._pallas_eqns: set = set()   # double-count equations
        self._act_dot_eqns: set = set()

    # -- generic walk ------------------------------------------------------
    def walk(self, jaxpr: core.Jaxpr,
             in_taints: Sequence[FrozenSet[str]]) -> List[FrozenSet[str]]:
        env = {}

        def read(v):
            if isinstance(v, core.Literal):
                return EMPTY
            return env.get(v, EMPTY)

        def write(v, t):
            if t:
                env[v] = frozenset(t)

        assert len(jaxpr.invars) == len(in_taints), \
            (len(jaxpr.invars), len(in_taints))
        for v, t in zip(jaxpr.invars, in_taints):
            write(v, t)
        for eqn in jaxpr.eqns:
            ins = [read(v) for v in eqn.invars]
            union = frozenset().union(*ins) if ins else EMPTY
            name = eqn.primitive.name
            if name == "pallas_call":
                # quantization barrier: rounded-kernel outputs are clean
                self._pallas_eqns.add(id(eqn))
                outs = [EMPTY] * len(eqn.outvars)
            elif name == "dot_general":
                self._dot_eqns.add(id(eqn))
                if not union:
                    self._act_dot_eqns.add(id(eqn))
                self.reached |= union
                outs = [union] * len(eqn.outvars)
            elif name == "scan":
                outs = self._walk_scan(eqn, ins)
            elif name == "while":
                outs = self._walk_while(eqn, ins)
            elif name == "cond":
                outs = self._walk_cond(eqn, ins)
            else:
                outs = self._walk_generic(eqn, ins, union)
            for v, t in zip(eqn.outvars, outs):
                write(v, t)
        return [read(v) for v in jaxpr.outvars]

    # -- control flow ------------------------------------------------------
    # Carry-feedback fixpoints are monotone over a finite taint lattice, so
    # they converge in at most (#distinct leaf names × #carries) merges;
    # the cap is a runaway guard.  A silent cap-exhaustion could UNDER-taint
    # (an offending dot_general reported clean), so it is a hard error.
    _FIXPOINT_CAP = 64

    def _walk_scan(self, eqn, ins):
        nc = eqn.params["num_consts"]
        nk = eqn.params["num_carry"]
        body = eqn.params["jaxpr"].jaxpr
        taints = list(ins)              # consts + init-carry + xs (1:1)
        outs = self.walk(body, taints)
        for _ in range(self._FIXPOINT_CAP):
            merged = [taints[nc + i] | outs[i] for i in range(nk)]
            if merged == taints[nc:nc + nk]:
                return outs[:len(eqn.outvars)]
            taints[nc:nc + nk] = merged
            outs = self.walk(body, taints)
        raise RuntimeError("audit: scan carry taint did not converge "
                           f"within {self._FIXPOINT_CAP} iterations")

    def _walk_while(self, eqn, ins):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"].jaxpr
        consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _ in range(self._FIXPOINT_CAP):
            outs = self.walk(body, list(consts) + carry)
            merged = [c | o for c, o in zip(carry, outs)]
            if merged == carry:
                return carry
            carry = merged
        raise RuntimeError("audit: while carry taint did not converge "
                           f"within {self._FIXPOINT_CAP} iterations")

    def _walk_cond(self, eqn, ins):
        branches = eqn.params["branches"]
        outs = [EMPTY] * len(eqn.outvars)
        for br in branches:
            b_outs = self.walk(_inner_jaxpr(br), ins[1:])
            outs = [a | b for a, b in zip(outs, b_outs)]
        return outs

    def _walk_generic(self, eqn, ins, union):
        """pjit / remat / custom-vjp / shard_map / closed_call all pass
        their operands 1:1; unknown sub-jaxpr carriers fall back to
        conservative all-union taint (sound: may over-flag, never
        under-flag)."""
        subs = []
        for v in eqn.params.values():
            j = _inner_jaxpr(v)
            if j is not None:
                subs.append(j)
            elif isinstance(v, (tuple, list)):
                subs.extend(jj for jj in map(_inner_jaxpr, v)
                            if jj is not None)
        if not subs:
            return [union] * len(eqn.outvars)
        outs = [EMPTY] * len(eqn.outvars)
        for j in subs:
            if len(j.invars) == len(ins):
                j_outs = self.walk(j, ins)
            else:
                j_outs = self.walk(j, [union] * len(j.invars))
            got = j_outs[:len(eqn.outvars)]
            got += [union] * (len(eqn.outvars) - len(got))
            outs = [a | b for a, b in zip(outs, got)]
        return outs


def audit_fn(fn: Callable, params, *args) -> AuditReport:
    """Trace ``fn(params, *args)`` and audit its jaxpr.

    Every leaf of ``params`` (the first argument) is a taint source named
    by its tree path; the remaining arguments are untainted inputs.  Run
    with the policy active (e.g. ``binary8-paper``) and with ``fn``
    including the backward pass (``jax.grad``) to audit training coverage.
    """
    closed = jax.make_jaxpr(fn)(params, *args)
    p_names = _leaf_paths(params)
    n_rest = len(jax.tree_util.tree_leaves(args))
    taints = [frozenset({n}) for n in p_names] + [EMPTY] * n_rest
    w = _Walker()
    w.walk(closed.jaxpr, taints)
    return AuditReport(reached=frozenset(w.reached),
                       n_dot_general=len(w._dot_eqns),
                       n_quantized_calls=len(w._pallas_eqns),
                       n_act_dot_general=len(w._act_dot_eqns))


def assert_coverage(report: AuditReport,
                    allowed: FrozenSet[str] = ALLOWED_FP32_LEAVES,
                    min_quantized_calls: int = 1) -> None:
    """Raise AssertionError naming every non-allowlisted offender."""
    bad = sorted(report.offenders(allowed))
    assert not bad, (
        "full-precision weight GEMM(s) outside the quantized kernels; "
        f"param leaves reaching dot_general: {bad}")
    assert report.n_quantized_calls >= min_quantized_calls, (
        "audit saw no quantized pallas_call — policy not active?"
        f" ({report.n_quantized_calls} < {min_quantized_calls})")
