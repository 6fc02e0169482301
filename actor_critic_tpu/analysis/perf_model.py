"""Whole-repo performance model for the perf passes (ISSUE 15).

Sibling of `thread_model.py` (threads), `process_model.py` (ranks) and
`dtype_model.py` (numerics): pure-`ast` facts the three performance
passes in `analysis/perf.py` share, extracted once per run. The repo's
headline perf claims are CONTRACTS — "steady-state consumption
transfers zero bytes" (PR 13), "a swap never recompiles" (PR 10) — and
this model names the source regions those contracts live in:

- **Hot regions.** A module is hot when its basename is in
  `HOT_BASENAMES` (the step-loop owners ISSUE 5 named) or it carries a
  `# jaxlint: hot-module` pragma. Within ANY module, `step_loops`
  additionally resolves the loops that dispatch a compiled program each
  iteration — the steady-state bodies where a host↔device crossing is
  paid per step, not once.

- **Program bindings.** `named_jit_sites` (jitinfo.py) only sees direct
  `jax.jit` wraps, but this codebase overwhelmingly builds its programs
  through FACTORIES (`update = ppo.make_async_update_step(...)`): the
  jit lives inside the factory, the dispatch loop lives in the caller,
  and no single-module pass can connect them. `factory_programs` scans
  every module for factory defs whose return value is a jit-wrapped
  callable (direct `return jax.jit(f)`, a returned `@jax.jit`/
  `@partial(jax.jit, ...)`-decorated inner def, or a returned local jit
  wrap), recording the donation configuration; `program_bindings` then
  resolves `name = factory(...)` assignments per scope, so the passes
  know that `update(...)` at a call site dispatches a compiled program
  — and whether that program donates.

- **Crossing classification.** `crossing_kind` names host↔device
  crossing expressions: the device→host syncs host-sync always matched
  (`.item()`, `np.asarray`, `block_until_ready`, `float()`/`int()`
  coercions) plus `jax.device_get` and the host→device upload family
  (`jnp.array`/`jnp.asarray`/`jax.device_put`) — each a transfer paid
  per iteration when it sits in a steady-state loop.

The runtime companion is `analysis/perfsan.py`, which counts the same
quantities (dispatches, transfers, transferred bytes, recompiles) on
the REAL programs against `perf_budgets.json`.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Iterable, Optional

from actor_critic_tpu.analysis.core import ModuleInfo, target_names
from actor_critic_tpu.analysis.jitinfo import (
    JitSite,
    collect_jit_sites,
    is_jax_jit_expr,
    named_jit_sites,
)

# The step-loop owners (ISSUE 5's host-sync scope, inherited verbatim).
# Other modules opt in via the `# jaxlint: hot-module` pragma.
HOT_BASENAMES = {"host_loop.py", "ppo.py", "compile_cache.py"}

_LOOPS = (ast.For, ast.AsyncFor, ast.While)
_SYNC_FREE_CALLS = {"len", "round", "abs"}  # cheap host-side builtins

# Factory names that return compiled programs follow one convention in
# this repo: make_<something about stepping/updating the system>.
_FACTORY_RE = re.compile(
    r"^make_\w*(update|step|train|ingest|enqueue|act|eval|rollout)\w*$"
)

# Argument names that denote large recycled device state — the
# donate-eligible family donation-discipline prices.
BUFFER_NAME_RE = re.compile(
    r"(state|ring|replay|buffer|storage|learner|params|opt)", re.I
)


def is_hot_module(mod: ModuleInfo) -> bool:
    basename = mod.relpath.rsplit("/", 1)[-1]
    return basename in HOT_BASENAMES or mod.hot_module


def in_loop(mod: ModuleInfo, node: ast.AST) -> Optional[ast.AST]:
    """The innermost real loop ancestor (comprehensions alone do not
    count — a lone dict-comp runs once per CALL, not per step), or
    None."""
    for anc in mod.ancestors(node):
        if isinstance(anc, _LOOPS):
            return anc
    return None


# ---------------------------------------------------------------------------
# factory programs: jit-wrapped callables returned by make_* factories
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramInfo:
    """One compiled-program source: a factory (or direct jit wrap)
    whose result is dispatched at call sites. `key` is the last-two-
    component dotted name call sites resolve against
    ("ppo.make_async_update_step")."""

    key: str
    relpath: str
    lineno: int
    donates: bool
    donated_positions: tuple[int, ...]


def _returned_jit_site(
    mod: ModuleInfo, fn: ast.AST
) -> Optional[JitSite]:
    """The JitSite a factory def returns, or None. Recognizes
    `return jax.jit(f, ...)`, `return <name>` where <name> is a local
    jit wrap or a jit-decorated inner def, and `return partial-jit`
    spellings — the shapes the repo's make_* factories actually use."""
    local_sites = {
        s.name: s
        for s in collect_jit_sites(mod)
        if s.name and _contains(fn, s.lineno)
    }
    for node in ast.walk(fn):
        if not isinstance(node, ast.Return) or node.value is None:
            continue
        value = node.value
        if isinstance(value, ast.Call) and is_jax_jit_expr(mod, value.func):
            for s in collect_jit_sites(mod):
                if s.lineno == value.lineno and not s.name:
                    return s
            site = JitSite("", value.lineno)
            return site
        if isinstance(value, ast.Name) and value.id in local_sites:
            return local_sites[value.id]
    return None


def _contains(fn: ast.AST, lineno: int) -> bool:
    return (
        getattr(fn, "lineno", 0)
        <= lineno
        <= (getattr(fn, "end_lineno", 0) or 0)
    )


def factory_programs(modules: Iterable[ModuleInfo]) -> dict[str, ProgramInfo]:
    """key ("<module stem>.<factory name>") → ProgramInfo for every
    factory def in the repo whose return value is a compiled program.
    Bare factory names are registered too, for same-module call sites
    (`update = make_host_update_step(...)`)."""
    out: dict[str, ProgramInfo] = {}
    for mod in modules:
        stem = mod.relpath.rsplit("/", 1)[-1].removesuffix(".py")
        for node in mod.tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _FACTORY_RE.match(node.name):
                continue
            site = _returned_jit_site(mod, node)
            if site is None:
                continue
            info = ProgramInfo(
                key=f"{stem}.{node.name}",
                relpath=mod.relpath,
                lineno=node.lineno,
                donates=site.donates,
                donated_positions=site.donated_positions(),
            )
            out[info.key] = info
    return out


def program_bindings(
    mod: ModuleInfo,
    scope: ast.AST,
    factories: dict[str, ProgramInfo],
) -> dict[str, ProgramInfo]:
    """name → ProgramInfo for names bound in `scope` from a factory
    call (`update = ppo.make_async_update_step(...)`) or a direct local
    jit wrap (folded in as ProgramInfo so the passes see one shape)."""
    out: dict[str, ProgramInfo] = {}
    stem = mod.relpath.rsplit("/", 1)[-1].removesuffix(".py")
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign) or not isinstance(
            node.value, ast.Call
        ):
            continue
        dotted = mod.dotted(node.value.func)
        if dotted is None:
            continue
        # Dotted call sites resolve by their own last-two components;
        # BARE names resolve only against THIS module's factories — a
        # bare `make_train_step(...)` in module B must never inherit
        # module A's donation config just because the names collide
        # (the repo has five make_train_step defs).
        if "." in dotted:
            info = factories.get(".".join(dotted.split(".")[-2:]))
        else:
            info = factories.get(f"{stem}.{dotted}")
        if info is None:
            continue
        for tgt in node.targets:
            for name in target_names(tgt):
                out[name] = info
    # Named jit wraps resolve scope-aware: a site bound INSIDE this
    # scope wins over a module-level one of the same name, and a site
    # local to a DIFFERENT function never leaks in (two functions may
    # each bind `run = jax.jit(...)` with different donation configs).
    top_defs = [
        n for n in mod.tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for name, site in _scoped_jit_sites(mod, scope, top_defs).items():
        out[name] = ProgramInfo(
            key=name,
            relpath=mod.relpath,
            lineno=site.lineno,
            donates=site.donates,
            donated_positions=site.donated_positions() or (
                (0,) if site.donates else ()
            ),
        )
    return out


def _scoped_jit_sites(
    mod: ModuleInfo, scope: ast.AST, top_defs: list[ast.AST]
) -> dict[str, JitSite]:
    module_level: dict[str, JitSite] = {}
    in_scope: dict[str, JitSite] = {}
    for site in sorted(collect_jit_sites(mod), key=lambda s: s.lineno):
        if not site.name:
            continue
        if not isinstance(scope, ast.Module) and _contains(
            scope, site.lineno
        ):
            in_scope[site.name] = site
        elif not any(_contains(d, site.lineno) for d in top_defs):
            module_level[site.name] = site
    return {**module_level, **in_scope}


# ---------------------------------------------------------------------------
# step loops: the steady-state dispatch bodies
# ---------------------------------------------------------------------------


def step_loops(
    mod: ModuleInfo, factories: dict[str, ProgramInfo]
) -> list[ast.AST]:
    """Loops whose body dispatches a compiled program (a program
    binding or local jit site) — the per-step regions where a crossing
    or a stray dispatch is paid every iteration. Resolution is
    name-based within the enclosing top-level scope, so a loop calling
    a program received as an opaque parameter stays out (no evidence)."""
    out: list[ast.AST] = []
    bindings_by_scope: dict[int, dict[str, ProgramInfo]] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, _LOOPS):
            continue
        scope = mod.scope_of(node)
        key = id(scope)
        if key not in bindings_by_scope:
            bindings_by_scope[key] = program_bindings(mod, scope, factories)
        bindings = bindings_by_scope[key]
        if not bindings:
            continue
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id in bindings
            ):
                out.append(node)
                break
    return out


def in_step_loop(
    mod: ModuleInfo, node: ast.AST, loops: list[ast.AST]
) -> bool:
    ids = {id(l) for l in loops}
    return any(id(anc) in ids for anc in mod.ancestors(node))


# ---------------------------------------------------------------------------
# crossing classification (host-sync's taxonomy + uploads + device_get)
# ---------------------------------------------------------------------------


def crossing_kind(
    mod: ModuleInfo, call: ast.Call
) -> Optional[tuple[str, str]]:
    """(description, direction) of the host↔device crossing this call
    performs, or None. direction is "d2h" (a sync: the host blocks on
    the device) or "h2d" (an upload: bytes cross per iteration)."""
    dotted = mod.dotted(call.func)
    if isinstance(call.func, ast.Attribute):
        if call.func.attr == "item" and not call.args:
            return "`.item()`", "d2h"
        if call.func.attr == "block_until_ready":
            return "`block_until_ready`", "d2h"
    if dotted == "jax.block_until_ready":
        return "`jax.block_until_ready`", "d2h"
    if dotted == "jax.device_get":
        return "`jax.device_get`", "d2h"
    if dotted in ("numpy.asarray", "numpy.array"):
        return f"`{dotted.replace('numpy', 'np')}`", "d2h"
    if dotted == "jax.device_put":
        return "`jax.device_put`", "h2d"
    if dotted in ("jax.numpy.array", "jax.numpy.asarray"):
        return f"`jnp.{dotted.rsplit('.', 1)[-1]}`", "h2d"
    if dotted in ("float", "int") and call.args:
        arg = call.args[0]
        if isinstance(arg, ast.Constant):
            return None
        if isinstance(arg, ast.Call):
            inner = mod.dotted(arg.func) or ""
            if (
                inner.startswith("numpy.")
                or inner.startswith("math.")
                or inner in _SYNC_FREE_CALLS
            ):
                return None  # numpy/host math — no device involved
        return f"`{dotted}()`", "d2h"
    return None


# ---------------------------------------------------------------------------
# eager device ops (dispatch-granularity's raw material)
# ---------------------------------------------------------------------------

_DEVICE_NAMESPACES = ("jax.numpy", "jax.nn", "jax.lax")
# The upload/constructor family transfer-discipline already owns — the
# granularity pass must not double-report it.
_TRANSFER_ATTRS = {"array", "asarray", "device_put"}


def eager_device_call(mod: ModuleInfo, call: ast.Call) -> Optional[str]:
    """The op name when `call` is a device-namespace math call
    dispatched EAGERLY (one tiny XLA program per evaluation), or None.
    Upload spellings are excluded (transfer-discipline's class)."""
    if not isinstance(call.func, ast.Attribute):
        return None
    base = mod.dotted(call.func.value)
    if base not in _DEVICE_NAMESPACES:
        return None
    if call.func.attr in _TRANSFER_ATTRS:
        return None
    return call.func.attr


def jit_traced_defs(mod: ModuleInfo) -> set[int]:
    """id()s of def nodes that are jit-traced (the wrapped def of any
    jit site) — eager-op findings must skip code that actually runs
    inside a program."""
    out: set[int] = set()
    for site in collect_jit_sites(mod):
        if site.func_def is not None:
            out.add(id(site.func_def))
    return out


def inside_traced_def(
    mod: ModuleInfo, node: ast.AST, traced: set[int]
) -> bool:
    if id(node) in traced:
        return True
    return any(id(anc) in traced for anc in mod.ancestors(node))
