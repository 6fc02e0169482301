"""shape_model: shape/padding/mask facts for the padding-discipline
passes (analysis/shapes.py) — the SHAPES sibling of thread_model /
process_model / dtype_model / perf_model.

The framework stabilizes shapes by padding everywhere the hardware
wants tiles: `pad_to_bucket` widens ragged serving/chunk batches to a
bucket ladder, `ops.pallas_scan._pad_lanes` lane-pads ragged env
batches to the 128-lane Mosaic tile ("compute junk, slice it away"),
and the mixture fleet zero-pads heterogeneous obs behind per-type
validity masks. Each producer has a DISCIPLINE that keeps the junk
lanes out of the math:

- a **mask** rides along (`padded, mask = pad_to_bucket(...)`) and
  every reduction over the widened axis multiplies/`where`s it in, or
- the consumer **slices back** to the valid prefix (`out[:n]`,
  `adv[:, :E]`) before anything observes the padded lanes.

This module inventories, per statement-ordered scope (the same units
dtype_model analyzes):

- **pad bindings** — names bound from a padding producer call
  (`pad_to_bucket` unpack, `_pad_lanes` unpack, `jnp.pad`/`np.pad`),
  each carrying the mask name bound alongside it (None when the mask
  was discarded with `_`), threaded through shape-preserving wrappers
  (`asarray`/`astype`/`device_put`/...) and CLEARED by a slice-back or
  any other rebind;
- **mask names** — the second `pad_to_bucket` unpack element plus any
  identifier that self-describes as a mask (`*mask*`, `*valid*`,
  `*count*`);
- **slice-back sites** — names that appear under a `Slice` subscript
  anywhere in the scope (`np.asarray(out)[:n]` counts for `out`): the
  evidence that a padded result is cut before it is observed.

Everything is pure `ast` (core.py's contract: scanned code is never
imported). Like the siblings, the model is deliberately name-local and
conservative: a binding is only "padded" when a producer call visibly
creates it in the same scope, so the passes built on top have the
precision to run with an EMPTY baseline.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Iterable, Optional

from actor_critic_tpu.analysis.core import ModuleInfo, target_names

# ---------------------------------------------------------------------------
# Vocabulary
# ---------------------------------------------------------------------------

# Producer call suffixes (matched against core's alias-resolved dotted
# name): the batch-axis bucket pad, the Mosaic lane pad, and the raw
# jnp/np pad primitive.
BUCKET_PAD_SUFFIX = "pad_to_bucket"
LANE_PAD_SUFFIX = "_pad_lanes"
RAW_PAD_ROOTS = ("jax.numpy", "numpy", "jax")  # <root>.pad / <root>...pad

# Defs that ARE the producers (and their unit-sized helpers): the pad
# they construct is their contract, not a leak — the passes skip their
# bodies entirely.
PRODUCER_DEF_NAMES = {"pad_to_bucket", "_pad_lanes", "_pad"}

# Calls that preserve the padded axis (and therefore propagate the
# binding): staging/casting wrappers between the producer and the
# consumer seam.
_PRESERVING_SUFFIXES = (
    "asarray", "array", "device_put", "device_get", "block_until_ready",
    "astype", "copy", "stop_gradient",
)

# Reductions that collapse an axis — the calls pad-mask-discipline
# audits when their operand is a padded binding.
REDUCTION_NAMES = {
    "mean", "sum", "max", "min", "prod", "std", "var", "median",
    "average", "amax", "amin", "argmax", "argmin", "nanmean", "nansum",
    "logsumexp", "softmax", "log_softmax",
}

# Commit-point callees for slice-before-commit: once a padded buffer
# crosses one of these it is durable/visible (published params, a
# checkpoint, a data-plane slot, a serving response, a socket) and the
# junk lanes are someone else's wrong answer.
COMMIT_NAMES = {
    "publish", "save", "save_checkpoint", "swap", "write_params",
    "put", "put_nowait", "enqueue", "send", "sendall", "respond",
    "write", "wfile_write", "set_result",
}

# Identifier fragments that self-describe as pad-validity metadata: a
# call that passes one of these alongside the padded array is keeping
# the mask-propagation contract.
MASK_FRAGMENTS = ("mask", "valid", "count")

# Alias-resolved roots treated as library namespaces: elementwise
# library math preserves lanes (and its reductions are pad-mask-
# discipline's domain), so mask-propagation only audits USER seams.
_LIB_ROOTS = {
    "jax", "numpy", "math", "functools", "np", "jnp", "scipy",
}


# ---------------------------------------------------------------------------
# Small AST predicates shared by the passes
# ---------------------------------------------------------------------------


def call_name(mod: ModuleInfo, node: ast.Call) -> Optional[str]:
    """Alias-resolved dotted name of a call's callee; for curried calls
    (`pl.pallas_call(...)(args)`) the INNER callee's name — that is the
    namespace that decides library-vs-user."""
    fn = node.func
    while isinstance(fn, ast.Call):
        fn = fn.func
    return mod.dotted(fn)


def bare_names(expr: ast.AST) -> set[str]:
    """Bare Name loads in an expression, excluding attribute bases
    (`x.shape` uses `x` structurally, `jnp.mean` is a namespace) — the
    same notion numerics.py keys its models on."""
    out: set[str] = set()
    attr_bases: set[int] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and isinstance(
            node.value, ast.Name
        ):
            attr_bases.add(id(node.value))
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and id(node) not in attr_bases:
            out.add(node.id)
    return out


def is_maskish(name: str) -> bool:
    low = name.lower()
    return any(frag in low for frag in MASK_FRAGMENTS)


def _is_lib_root(mod: ModuleInfo, dotted: Optional[str]) -> bool:
    if not dotted:
        return False
    root = dotted.split(".")[0]
    return root in _LIB_ROOTS


def is_raw_pad_call(mod: ModuleInfo, node: ast.Call) -> bool:
    """`jnp.pad(...)` / `np.pad(...)` (alias-resolved)."""
    dotted = mod.dotted(node.func)
    if not dotted or not dotted.endswith(".pad"):
        return False
    return _is_lib_root(mod, dotted)


def producer_kind(mod: ModuleInfo, node: ast.Call) -> Optional[str]:
    """"pad_to_bucket" | "_pad_lanes" | "pad" for producer calls."""
    dotted = mod.dotted(node.func)
    if dotted:
        if dotted.split(".")[-1] == BUCKET_PAD_SUFFIX:
            return "pad_to_bucket"
        if dotted.split(".")[-1] == LANE_PAD_SUFFIX:
            return "_pad_lanes"
    if is_raw_pad_call(mod, node):
        return "pad"
    return None


def is_preserving_call(mod: ModuleInfo, node: ast.Call) -> bool:
    dotted = mod.dotted(node.func)
    if dotted is None:
        return False
    return dotted.split(".")[-1] in _PRESERVING_SUFFIXES


def reduction_operand(
    mod: ModuleInfo, node: ast.Call
) -> Optional[ast.AST]:
    """The reduced expression when `node` is a reduction call, else
    None. Covers `jnp.mean(x)` (library function, first positional arg)
    and `x.mean()` (method form, the receiver)."""
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr in REDUCTION_NAMES:
        dotted = mod.dotted(fn)
        if dotted and _is_lib_root(mod, dotted):
            return node.args[0] if node.args else None
        # method form: the receiver is the operand
        return fn.value
    if isinstance(fn, ast.Name):
        resolved = mod.aliases.get(fn.id, fn.id)
        if resolved.split(".")[-1] in REDUCTION_NAMES and _is_lib_root(
            mod, resolved
        ):
            return node.args[0] if node.args else None
    return None


def has_valid_slice(expr: ast.AST, names: set[str]) -> bool:
    """A `Slice` subscript over one of `names` inside `expr`
    (`x[:n]`, `adv[:, :E]`, `np.asarray(out)[:n]`)."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Subscript):
            continue
        if not _contains_slice(node.slice):
            continue
        if bare_names(node.value) & names:
            return True
    return False


def _contains_slice(node: ast.AST) -> bool:
    if isinstance(node, ast.Slice):
        return True
    if isinstance(node, ast.Tuple):
        return any(_contains_slice(e) for e in node.elts)
    return False


def has_mask_guard(
    mod: ModuleInfo, expr: ast.AST, masks: set[str]
) -> bool:
    """Whether `expr` applies a validity mask to what it reduces: a
    multiply whose other side is a mask binding/maskish name, or a
    `where(mask, ...)` select."""

    def maskish(e: ast.AST) -> bool:
        return any(n in masks or is_maskish(n) for n in bare_names(e))

    for node in ast.walk(expr):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            if maskish(node.left) or maskish(node.right):
                return True
        if isinstance(node, ast.Call):
            dotted = mod.dotted(node.func)
            if dotted and dotted.split(".")[-1] == "where" and node.args:
                if maskish(node.args[0]):
                    return True
    return False


# ---------------------------------------------------------------------------
# Per-scope flow model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PadBinding:
    """One name currently carrying a padded array."""

    name: str
    producer: str  # "pad_to_bucket" | "_pad_lanes" | "pad"
    mask: Optional[str]  # mask bound alongside (None = discarded)
    lineno: int  # producer site


@dataclasses.dataclass
class ScopeFlow:
    """Statement-ordered padding facts for one scope."""

    scope: ast.AST
    stmts: list  # ordered ast.stmt list (nested blocks inlined)
    env_before: dict  # id(stmt) -> {name: PadBinding}
    masks: set  # mask names bound in this scope
    sliced: set  # names observed under a Slice subscript anywhere


def iter_scopes(mod: ModuleInfo) -> Iterable[ast.AST]:
    """Top-level functions plus methods of top-level classes, then the
    module itself — the same units dtype_model iterates."""
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield sub
    yield mod.tree


def _scope_stmts(mod: ModuleInfo, scope: ast.AST) -> list:
    """All statements belonging to `scope`, in source order. Function
    scopes include their nested defs' bodies (the closure IS the scope's
    dataflow — serving's `xla_once` pattern); the module scope owns only
    what no top-level def/method claims."""
    if isinstance(scope, ast.Module):
        claimed: set[int] = set()
        for fn in iter_scopes(mod):
            if isinstance(fn, ast.Module):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.stmt):
                    claimed.add(id(node))
        stmts = [
            n
            for n in ast.walk(scope)
            if isinstance(n, ast.stmt) and id(n) not in claimed
        ]
    else:
        stmts = [
            n
            for n in ast.walk(scope)
            if isinstance(n, ast.stmt) and n is not scope
        ]
    stmts.sort(key=lambda n: (n.lineno, n.col_offset))
    return stmts


def _assign_parts(stmt: ast.stmt):
    """(targets, value) for the binding statements the flow threads."""
    if isinstance(stmt, ast.Assign):
        return stmt.targets, stmt.value
    if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
        return [stmt.target], stmt.value
    if isinstance(stmt, ast.AugAssign):
        return [stmt.target], stmt.value
    return None, None


def _unwrap_preserving(mod: ModuleInfo, expr: ast.AST) -> ast.AST:
    """Peel shape-preserving wrapper calls: `np.asarray(x)` -> `x`."""
    while isinstance(expr, ast.Call) and is_preserving_call(mod, expr):
        if len(expr.args) >= 1:
            expr = expr.args[0]
        else:
            break
    return expr


def _is_slice_of(mod: ModuleInfo, expr: ast.AST, names: set[str]) -> bool:
    """Whether `expr` IS (possibly wrapped) a Slice subscript of one of
    `names` — the slice-back that clears a padded binding."""
    expr = _unwrap_preserving(mod, expr)
    if isinstance(expr, ast.Subscript) and _contains_slice(expr.slice):
        return bool(bare_names(expr.value) & names)
    return False


def build_scope_flow(mod: ModuleInfo, scope: ast.AST) -> ScopeFlow:
    stmts = _scope_stmts(mod, scope)
    env: dict[str, PadBinding] = {}
    masks: set[str] = set()
    sliced: set[str] = set()
    env_before: dict[int, dict[str, PadBinding]] = {}

    # One up-front pass for slice-back evidence: consumers often slice
    # AFTER the seam the passes audit (`out = program(p, padded)` then
    # `return np.asarray(out)[:n]`), so this set is scope-global.
    for stmt in stmts:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Subscript) and _contains_slice(
                node.slice
            ):
                sliced |= bare_names(node.value)

    for stmt in stmts:
        env_before[id(stmt)] = dict(env)
        targets, value = _assign_parts(stmt)
        if targets is None:
            # for-loop / with-as targets rebind names opaquely
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                for n in target_names(stmt.target):
                    env.pop(n, None)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    if item.optional_vars is not None:
                        for n in target_names(item.optional_vars):
                            env.pop(n, None)
            continue
        names = [n for t in targets for n in target_names(t)]
        inner = _unwrap_preserving(mod, value)

        if isinstance(inner, ast.Call):
            kind = producer_kind(mod, inner)
        else:
            kind = None

        if kind == "pad_to_bucket":
            # `padded, mask = pad_to_bucket(...)`: first name padded,
            # second is its mask ("_" = discarded).
            tgt = targets[0]
            if isinstance(tgt, (ast.Tuple, ast.List)) and len(tgt.elts) == 2:
                pn = target_names(tgt.elts[0])
                mn = target_names(tgt.elts[1])
                mask = mn[0] if mn and mn[0] != "_" else None
                if mask:
                    masks.add(mask)
                for n in pn:
                    env[n] = PadBinding(n, kind, mask, stmt.lineno)
            else:
                for n in names:
                    env[n] = PadBinding(n, kind, None, stmt.lineno)
            continue
        if kind == "_pad_lanes":
            # every unpacked element is lane-padded; the discipline is
            # the downstream `[:, :E]` slice, not a mask.
            for n in names:
                if n != "_":
                    env[n] = PadBinding(n, kind, None, stmt.lineno)
            continue
        if kind == "pad":
            # raw jnp/np.pad — unless a mask multiply is applied in the
            # same expression (the mixture obs contract), the binding is
            # undisciplined padded data.
            if has_mask_guard(mod, value, masks):
                for n in names:
                    env.pop(n, None)
            else:
                for n in names:
                    env[n] = PadBinding(n, kind, None, stmt.lineno)
            continue

        padded_names = set(env)
        if padded_names and _is_slice_of(mod, value, padded_names):
            # slice-back: the target holds valid lanes only
            for n in names:
                env.pop(n, None)
            continue
        # propagation: alias or preserving wrapper of a padded name
        src = inner if isinstance(inner, ast.Name) else None
        if src is not None and src.id in env and len(names) == 1:
            env[names[0]] = dataclasses.replace(env[src.id], name=names[0])
            continue
        # any other rebind clears the padded fact (conservative)
        for n in names:
            env.pop(n, None)

    return ScopeFlow(
        scope=scope, stmts=stmts, env_before=env_before, masks=masks,
        sliced=sliced,
    )


# ---------------------------------------------------------------------------
# Per-module model (single-entry cache, the numerics _SHARED pattern)
# ---------------------------------------------------------------------------


_SHARED: dict = {}


def module_flows(mod: ModuleInfo) -> list[ScopeFlow]:
    """[ScopeFlow] for every scope in `mod`, cached per module so the
    three shapes passes build the model once."""
    entry = _SHARED.get("entry")
    # The entry holds the module itself, as the other passes' entries do: an
    # `id()` alone is handed to the next module once this one is collected.
    if entry is not None and entry[0] is mod:
        return entry[1]
    flows = [build_scope_flow(mod, scope) for scope in iter_scopes(mod)]
    _SHARED["entry"] = (mod, flows)
    return flows


def scope_name(scope: ast.AST) -> str:
    if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return scope.name
    return "<module>"


def is_producer_scope(scope: ast.AST) -> bool:
    """The producer defs themselves (pad_to_bucket, _pad_lanes, _pad):
    their bodies construct the pad on purpose."""
    return scope_name(scope) in PRODUCER_DEF_NAMES
