"""PTA002: static per-``pallas_call`` VMEM budget.

Each grid step of a Pallas kernel holds every BlockSpec window twice
(Mosaic double-buffers the in/out DMA windows) plus its scratch. A site
whose statically-priced windows exceed the budget will compile-fail (or
silently thrash) only on hardware — the interpret-mode CPU tests never
see it. This bit the repo twice before PR 4/PR 7 grew *fitters*
(``_fit_block_t``, ``_fit_bwd_flat_blocks``) that shrink blocks until
the windows fit a measured budget.

The rule prices every ``pallas_call``'s BlockSpec shapes (constant-folded
through straight-line assignments) at ``2 x prod(shape) x itemsize`` for
in/out specs plus ``prod x itemsize`` for VMEM scratch, and flags sites
over budget. Sites whose block shapes come from a registered fitter
(``_fit_*``) are exempt — sizing is the fitter's contract — and shapes
that cannot be resolved statically (caller-threaded block params) are
skipped rather than guessed.
"""
from __future__ import annotations

import ast

from .. import Rule, register
from .._astutil import (ConstEnv, FunctionIndex, call_ident, dotted_name,
                        enclosing_function, iter_calls, keyword,
                        resolve_local_call)

# conservative ceiling: the largest fitted budget in tree is the dense
# flash backward's 52 MB scratch+window set; anything statically priced
# above this is far outside what any TPU generation's scoped VMEM plus
# compiler spilling absorbs, and must route through a fitter instead.
BUDGET_BYTES = 64 * 1024 * 1024

# itemsize when a BlockSpec's operand dtype is unknown (f32 accumulators
# dominate the kernels here; bf16 operands under-price by 2x, which only
# makes the rule more permissive, never a false positive)
DEFAULT_ITEMSIZE = 4

_DTYPE_SIZES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}

# names a block-shape element may come from to mark the site fitter-sized
FITTER_PREFIX = "_fit"
REGISTERED_FITTERS = frozenset({"_fit_block_t", "_fit_bwd_flat_blocks",
                               "_fit_paged_kv_blocks",
                               "_fit_paged_verify_blocks",
                               "_fit_paged_prefill_blocks"})


def _is_fitter(name):
    return name is not None and (name in REGISTERED_FITTERS
                                 or name.startswith(FITTER_PREFIX))


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _fitter_derived_names(func):
    """Names assigned (directly or via tuple unpack) from a _fit_* call
    anywhere in the enclosing function."""
    out = set()
    if func is None:
        return out
    for node in ast.walk(func):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        calls = [c for c in ast.walk(value) if isinstance(c, ast.Call)]
        if not any(_is_fitter(call_ident(c)) for c in calls):
            continue
        for tgt in node.targets:
            out.update(_names_in(tgt))
    return out


def _scratch_itemsize(call):
    if len(call.args) >= 2:
        name = (dotted_name(call.args[1]) or "").rsplit(".", 1)[-1]
        return _DTYPE_SIZES.get(name, DEFAULT_ITEMSIZE)
    return DEFAULT_ITEMSIZE


@register
class VmemBudgetRule(Rule):
    code = "PTA002"
    title = "vmem-budget"
    rationale = ("statically-priced BlockSpec windows over the VMEM "
                 "budget compile-fail only on hardware; block sizing "
                 "must route through a registered fitter (_fit_*)")
    scope = ("paddle_tpu/ops", "paddle_tpu/parallel/")

    budget = BUDGET_BYTES

    def check_module(self, module):
        index = FunctionIndex(module.tree)
        for call in module.calls:
            if call_ident(call) != "pallas_call":
                continue
            func = enclosing_function(call)
            env = ConstEnv(module.tree, func)
            fitted = _fitter_derived_names(func)
            total, unresolved, fitter_routed = self._price_site(
                call, env, fitted)
            if fitter_routed:
                continue  # the fitter owns the budget for this site
            if unresolved:
                # caller-threaded blocks: re-price per intra-module call
                # site with the caller's arguments bound to the helper's
                # parameters (the dataflow hop v1 could not make)
                if func is not None and index.get(func.name) is func:
                    yield from self._reprice_at_callers(
                        module, call, func, fitted, index)
                continue
            if total > self.budget:
                yield self.finding(
                    module, call,
                    f"pallas_call windows statically price at "
                    f"{total / 2**20:.0f} MiB (double-buffered in/out "
                    f"specs + scratch) > {self.budget / 2**20:.0f} MiB "
                    f"budget; shrink blocks or route sizing through a "
                    f"registered fitter (_fit_*)")

    def _price_site(self, call, env, fitted):
        """(total_bytes, unresolved, fitter_routed) for one pallas_call."""
        windows = []
        unresolved = False
        fitter_routed = False
        for key in ("in_specs", "out_specs"):
            kw = keyword(call, key)
            if kw is None:
                continue
            for spec in iter_calls(kw.value):
                ident = call_ident(spec)
                if ident == "BlockSpec" and spec.args and \
                        isinstance(spec.args[0], (ast.Tuple, ast.List)):
                    prod, state = self._price(spec.args[0], env, fitted)
                    if state == "fitted":
                        fitter_routed = True
                    elif state == "unknown":
                        unresolved = True
                    else:
                        windows.append(prod * DEFAULT_ITEMSIZE * 2)
        kw = keyword(call, "scratch_shapes")
        if kw is not None:
            for spec in iter_calls(kw.value):
                if call_ident(spec) not in ("VMEM", "SMEM"):
                    continue
                if not spec.args or not isinstance(
                        spec.args[0], (ast.Tuple, ast.List)):
                    continue
                prod, state = self._price(spec.args[0], env, fitted)
                if state == "fitted":
                    fitter_routed = True
                elif state == "unknown":
                    unresolved = True
                else:
                    windows.append(prod * _scratch_itemsize(spec))
        return sum(windows), unresolved, fitter_routed

    def _reprice_at_callers(self, module, pallas_call, helper, fitted,
                            index):
        """Re-price a caller-threaded pallas_call at each intra-module
        call site of its enclosing helper, with the site's constant-
        resolvable arguments bound to the helper's parameters."""
        for site in module.calls:
            resolved = resolve_local_call(site, index)
            if resolved is None or resolved[0] is not helper:
                continue
            caller_env = ConstEnv(module.tree, enclosing_function(site))
            bindings = {}
            for pname, arg in resolved[1].items():
                val = caller_env.resolve(arg)
                if isinstance(val, (int, float)):
                    bindings[pname] = ast.Constant(value=val)
            env = ConstEnv(module.tree, helper, bindings=bindings)
            total, unresolved, fitter_routed = self._price_site(
                pallas_call, env, fitted)
            if fitter_routed or unresolved:
                continue
            if total > self.budget:
                yield self.finding(
                    module, site,
                    f"call binds {helper.name}() block params so its "
                    f"pallas_call windows price at {total / 2**20:.0f} "
                    f"MiB > {self.budget / 2**20:.0f} MiB budget; shrink "
                    f"the blocks passed here or route sizing through a "
                    f"registered fitter (_fit_*)")

    @staticmethod
    def _price(shape_node, env, fitted_names):
        """(product, state) where state is 'const' | 'fitted' | 'unknown'."""
        prod = 1
        state = "const"
        for elt in shape_node.elts:
            names = _names_in(elt)
            if names & fitted_names:
                return 0, "fitted"
            val = env.resolve(elt)
            if val is None:
                state = "unknown"
            elif isinstance(val, (int, float)):
                prod *= max(int(val), 0)
        return prod, state
