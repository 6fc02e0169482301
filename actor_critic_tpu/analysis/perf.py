"""Performance passes: transfer-discipline, donation-discipline,
dispatch-granularity (ISSUE 15 tentpole, static half).

The fifth analysis dimension (JAX correctness → threads → processes →
numerics → PERFORMANCE). The repo's perf claims are contracts — PR 13's
device plane promises "steady-state consumption transfers zero bytes",
PR 10's gateway promises "a swap never recompiles" — and accelerated
deep-RL stacks live or die on keeping the hot loop on-accelerator
(arxiv 1803.02811; HEPPO-GAE, arxiv 2501.12703, shows the next wins are
pipeline/memory discipline). Each pass names one way those contracts
silently rot:

- **transfer-discipline** — host↔device crossings paid per step.
  Generalizes and ABSORBS ISSUE 5's host-sync pass (its check name
  remains resolvable as an alias; annotations and baseline fingerprints
  migrated): the device→host syncs it always matched (`.item()`,
  `np.asarray`, `block_until_ready`, `float()`/`int()` coercions) plus
  `jax.device_get` and the host→device upload family (`jnp.array` /
  `jnp.asarray` / `jax.device_put`), flagged inside any loop of a hot
  module and inside detected step loops (loops dispatching a compiled
  program) of every other module. One stray crossing in a steady-state
  body serializes the async pipeline or pays a crossing per block —
  exactly the regression class the PR 13 A/B measured at 1.5×.

- **donation-discipline** — donate-eligible buffers the program copies
  instead. (a) A compiled-program call site that REBINDS one of its own
  argument names (`state = step(state, ...)` — the recycled-buffer
  shape) through a program with NO donation: XLA must allocate a second
  buffer for the output and copy-preserve the input it could have
  reused, doubling live HBM for that state (the replay/ring/params
  family this repo recycles every iteration). (b) Donated-then-read
  NEAR-MISSES the donation-aliasing pass cannot see: a VIEW/alias bound
  from the donated tree before the donating call and read after it —
  the alias points into a buffer XLA already reused even though the
  donated name itself was properly rebound.

- **dispatch-granularity** — work that belongs inside ONE fused program
  dispatched as many. Python-level reductions (`sum`/`min`/`max`) over
  device values inside a step loop (one tiny dispatch per element plus
  a sync at the end), eager device-namespace math in a step-loop body
  outside any jit (each call is its own XLA program every iteration),
  and ≥2 distinct compiled programs dispatched in one loop body (the
  gather/update split the device plane exists to fuse).

Runtime companion: `analysis/perfsan.py` counts dispatches / transfers
/ transferred bytes / recompiles on the REAL steady-state programs
against the committed `perf_budgets.json` (scripts/perfsan.py, tier-1's
quick profile between numsan and pytest).
"""

from __future__ import annotations

import ast
from typing import Optional

from actor_critic_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    register_check,
    target_names,
)
from actor_critic_tpu.analysis import perf_model
from actor_critic_tpu.analysis.perf_model import (
    BUFFER_NAME_RE,
    ProgramInfo,
    crossing_kind,
    eager_device_call,
    factory_programs,
    in_loop,
    in_step_loop,
    inside_traced_def,
    is_hot_module,
    jit_traced_defs,
    program_bindings,
    step_loops,
)

TRANSFER_DISCIPLINE = "transfer-discipline"
DONATION_DISCIPLINE = "donation-discipline"
DISPATCH_GRANULARITY = "dispatch-granularity"

# Single-entry shared-model cache (the concurrency/distributed/numerics
# passes' `_SHARED` idiom): three registered checks, one factory table —
# plus per-module step loops and per-scope program bindings, which every
# pass re-needs — computed once per run.
_SHARED: dict = {}


def _shared_state(modules: list[ModuleInfo]) -> dict:
    key = tuple(id(m) for m in modules)
    entry = _SHARED.get("entry")
    if entry is not None and entry[0] == key:
        return entry[1]
    state = {
        "factories": factory_programs(modules),
        "loops": {},      # id(mod) -> step loops
        "bindings": {},   # (id(mod), id(scope)) -> program bindings
        "modules": list(modules),  # keep ids alive for the cache key
    }
    _SHARED["entry"] = (key, state)
    return state


def _loops_for(state: dict, mod: ModuleInfo) -> list:
    loops = state["loops"].get(id(mod))
    if loops is None:
        loops = step_loops(mod, state["factories"])
        state["loops"][id(mod)] = loops
    return loops


def _bindings_for(state: dict, mod: ModuleInfo, scope) -> dict:
    key = (id(mod), id(scope))
    bindings = state["bindings"].get(key)
    if bindings is None:
        bindings = program_bindings(mod, scope, state["factories"])
        state["bindings"][key] = bindings
    return bindings


# ---------------------------------------------------------------------------
# transfer-discipline
# ---------------------------------------------------------------------------


@register_check(
    TRANSFER_DISCIPLINE,
    "host<->device crossings (.item()/np.asarray/block_until_ready/"
    "float()/device_get syncs; jnp.array/device_put uploads) inside "
    "steady-state loop bodies — absorbs host-sync",
    scope="repo",
)
def check_transfer_discipline(modules: list[ModuleInfo]) -> list[Finding]:
    state = _shared_state(modules)
    findings: list[Finding] = []
    for mod in modules:
        hot = is_hot_module(mod)
        loops = _loops_for(state, mod)
        traced = jit_traced_defs(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            # Hot modules keep host-sync's scope (any loop); elsewhere
            # only detected step loops flag — straight-line setup code
            # crosses once, not per step.
            if hot:
                if in_loop(mod, node) is None:
                    continue
            elif not in_step_loop(mod, node, loops):
                continue
            # Jit-traced bodies execute as ONE compiled program: an
            # upload spelling there runs once at trace time, not per
            # iteration (the dispatch-granularity pass's filter).
            if inside_traced_def(mod, node, traced):
                continue
            kind = crossing_kind(mod, node)
            if kind is None:
                continue
            desc, direction = kind
            if direction == "d2h":
                msg = (
                    f"{desc} inside a steady-state loop blocks the host "
                    "on the device every iteration, serializing the "
                    "async dispatch pipeline — hoist it to the log "
                    "cadence, keep the value on device, or suppress "
                    "with the reason if the sync is deliberate"
                )
            else:
                msg = (
                    f"{desc} inside a steady-state loop re-pays the "
                    "host->device transfer every iteration (the PR 13 "
                    "device plane exists to remove exactly this class "
                    "— its A/B measured the relocation at 1.5x); keep "
                    "the buffer device-resident, or suppress with the "
                    "reason if this upload IS the data plane (and then "
                    "it must carry a perfsan transfer budget)"
                )
            findings.append(
                Finding(
                    TRANSFER_DISCIPLINE, mod.relpath,
                    node.lineno, node.col_offset, msg,
                    mod.enclosing_function(node),
                )
            )
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings


# ---------------------------------------------------------------------------
# donation-discipline
# ---------------------------------------------------------------------------


def _rebound_names(mod: ModuleInfo, call: ast.Call) -> set[str]:
    """Names (and dotted attribute paths) the enclosing statement
    rebinds to this call's result."""
    parent = mod.parent(call)
    out: set[str] = set()
    if isinstance(parent, ast.Assign):
        for tgt in parent.targets:
            out |= set(target_names(tgt))
            path = _attr_path(tgt)
            if path:
                out.add(path)
    elif isinstance(parent, (ast.AnnAssign, ast.AugAssign)):
        out |= set(target_names(parent.target))
        path = _attr_path(parent.target)
        if path:
            out.add(path)
    return out


def _attr_path(node: ast.AST) -> Optional[str]:
    """Dotted path of a Name/Attribute chain ("self._state"), or None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _arg_root(arg: ast.AST) -> Optional[str]:
    while isinstance(arg, (ast.Subscript, ast.Attribute)):
        arg = arg.value
    return arg.id if isinstance(arg, ast.Name) else None


def _undonated_findings(
    mod: ModuleInfo,
    bindings: dict[str, ProgramInfo],
    call: ast.Call,
) -> list[Finding]:
    """Shape (a): a program with NO donation whose call site rebinds
    one of its own argument names — the recycled-buffer family."""
    info = bindings.get(
        call.func.id if isinstance(call.func, ast.Name) else ""
    )
    if info is None or info.donates:
        return []
    rebound = _rebound_names(mod, call)
    if not rebound:
        return []
    recycled = []
    for arg in call.args:
        name = _arg_root(arg)
        if name is not None and name in rebound:
            recycled.append(name)
    if not recycled:
        return []
    looped = in_loop(mod, call) is not None
    bufferish = any(BUFFER_NAME_RE.search(n) for n in recycled)
    if not (looped or bufferish):
        return []
    names = ", ".join(f"`{n}`" for n in sorted(set(recycled)))
    return [
        Finding(
            DONATION_DISCIPLINE, mod.relpath,
            call.lineno, call.col_offset,
            f"{names} is recycled through compiled program "
            f"`{call.func.id}` (result rebinds the argument) with no "
            "donation: XLA allocates a fresh output buffer and "
            "copy-preserves an input nothing will read again — for a "
            "ring/replay/params-sized tree that doubles its live HBM "
            "every iteration; add donate_argnums (uncommit restored "
            "states first — the donation-aliasing contract), or "
            "suppress with the reason the copy is load-bearing",
            mod.enclosing_function(call),
        )
    ]


def _alias_read_findings(
    mod: ModuleInfo,
    bindings: dict[str, ProgramInfo],
    call: ast.Call,
    scope: ast.AST,
) -> list[Finding]:
    """Shape (b): the donated-then-read near-miss donation-aliasing
    cannot see — an alias/view bound FROM the donated tree before the
    donating call, read after it. The donated name itself may be
    properly rebound (so the aliasing pass stays quiet), but the alias
    still points into the reused buffer."""
    info = bindings.get(
        call.func.id if isinstance(call.func, ast.Name) else ""
    )
    if info is None or not info.donates:
        return []
    positions = info.donated_positions or (0,)
    donated_roots = {
        r
        for p in positions
        if p < len(call.args)
        for r in [_arg_root(call.args[p])]
        if r is not None
    }
    if not donated_roots:
        return []
    # aliases: `view = root` / `view = root[...]` / `view = root.attr`
    # bound BEFORE the call in the same scope
    aliases: dict[str, int] = {}
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign) or node.lineno >= call.lineno:
            continue
        value = node.value
        root = _arg_root(value) if not isinstance(value, ast.Call) else None
        if root in donated_roots:
            for tgt in node.targets:
                for name in target_names(tgt):
                    if name not in donated_roots:
                        aliases[name] = node.lineno
    if not aliases:
        return []
    # reads of an alias after the donating call, not rebound BETWEEN
    # the call and the read (a rebind after the read does not unpoison
    # the earlier dereference)
    out: list[Finding] = []
    rebind_lines: dict[str, list[int]] = {}
    for node in ast.walk(scope):
        if isinstance(node, ast.Assign) and node.lineno > call.lineno:
            for tgt in node.targets:
                for name in target_names(tgt):
                    rebind_lines.setdefault(name, []).append(node.lineno)
    own = {id(n) for n in ast.walk(call)}
    for node in ast.walk(scope):
        if (
            isinstance(node, ast.Name)
            and node.id in aliases
            and isinstance(node.ctx, ast.Load)
            and id(node) not in own
            and node.lineno > call.lineno
            and not any(
                call.lineno < ln <= node.lineno
                for ln in rebind_lines.get(node.id, ())
            )
            and not mod.exclusive_branches(call, node)
        ):
            out.append(
                Finding(
                    DONATION_DISCIPLINE, mod.relpath,
                    node.lineno, node.col_offset,
                    f"`{node.id}` aliases `{'/'.join(sorted(donated_roots))}`"
                    f" (bound at line {aliases[node.id]}) which was "
                    f"donated into `{call.func.id}` at line "
                    f"{call.lineno} — the donated name may be rebound, "
                    "but this view still points into a buffer XLA "
                    "already reused (the near-miss the donation-"
                    "aliasing pass cannot see); re-derive it from the "
                    "call's result",
                    mod.enclosing_function(node),
                )
            )
            break  # one finding per donating call names the class
    return out


@register_check(
    DONATION_DISCIPLINE,
    "recycled ring/replay/params buffers donate-eligible but undonated "
    "at compiled-program call sites; donated-then-read alias near-"
    "misses the donation-aliasing pass cannot see",
    scope="repo",
)
def check_donation_discipline(modules: list[ModuleInfo]) -> list[Finding]:
    state = _shared_state(modules)
    findings: list[Finding] = []
    for mod in modules:
        for call in ast.walk(mod.tree):
            if not isinstance(call, ast.Call) or not isinstance(
                call.func, ast.Name
            ):
                continue
            scope = mod.scope_of(call)
            bindings = _bindings_for(state, mod, scope)
            findings.extend(_undonated_findings(mod, bindings, call))
            findings.extend(
                _alias_read_findings(mod, bindings, call, scope)
            )
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings


# ---------------------------------------------------------------------------
# dispatch-granularity
# ---------------------------------------------------------------------------

_PY_REDUCERS = {"sum", "min", "max"}


def _gated_in_loop(mod: ModuleInfo, node: ast.AST, loop: ast.AST) -> bool:
    """Whether `node` sits inside a nested def/lambda or under an `if`
    BETWEEN itself and `loop` — conditional/cadence-gated work, not the
    unconditional per-iteration chain."""
    for anc in mod.ancestors(node):
        if anc is loop:
            return False
        if isinstance(
            anc, (ast.If, ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            return True
    return False


def _reduction_over_device(
    mod: ModuleInfo,
    bindings: dict[str, ProgramInfo],
    call: ast.Call,
) -> bool:
    """Builtin sum/min/max whose iterable mentions a compiled-program
    dispatch or a device-namespace call — a Python loop of tiny
    dispatches plus a final sync."""
    if not isinstance(call.func, ast.Name):
        return False
    if call.func.id not in _PY_REDUCERS or not call.args:
        return False
    for sub in ast.walk(call.args[0]):
        if not isinstance(sub, ast.Call):
            continue
        if eager_device_call(mod, sub) is not None:
            return True
        if isinstance(sub.func, ast.Name) and sub.func.id in bindings:
            return True
    return False


@register_check(
    DISPATCH_GRANULARITY,
    "Python-level reductions over device values, eager device-"
    "namespace math, and multi-program dispatch chains inside "
    "per-step loops — work that belongs in one fused program",
    scope="repo",
)
def check_dispatch_granularity(modules: list[ModuleInfo]) -> list[Finding]:
    state = _shared_state(modules)
    findings: list[Finding] = []
    for mod in modules:
        loops = _loops_for(state, mod)
        if not loops:
            continue
        traced = jit_traced_defs(mod)

        def bindings_for(node):
            return _bindings_for(state, mod, mod.scope_of(node))

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not in_step_loop(mod, node, loops):
                continue
            if inside_traced_def(mod, node, traced):
                continue
            bindings = bindings_for(node)
            if _reduction_over_device(mod, bindings, node):
                findings.append(
                    Finding(
                        DISPATCH_GRANULARITY, mod.relpath,
                        node.lineno, node.col_offset,
                        f"Python `{node.func.id}()` over device values "
                        "inside a step loop dispatches one tiny program "
                        "per element and syncs at the end, every "
                        "iteration — fold the reduction into the "
                        "compiled program (jnp.sum/min/max inside the "
                        "jit) or hoist it to the log cadence",
                        mod.enclosing_function(node),
                    )
                )
                continue
            op = eager_device_call(mod, node)
            if op is not None:
                findings.append(
                    Finding(
                        DISPATCH_GRANULARITY, mod.relpath,
                        node.lineno, node.col_offset,
                        f"eager `jnp.{op}` inside a step loop is its "
                        "own XLA program dispatched every iteration — "
                        "move it inside the step's jitted program (one "
                        "fused dispatch per block is the contract the "
                        "update-wall bench prices), or suppress with "
                        "the reason if this site is cold",
                        mod.enclosing_function(node),
                    )
                )
        # multi-program chains: >= 2 DISTINCT compiled programs
        # dispatched unconditionally in one step-loop body. Calls
        # inside nested defs/lambdas (helper closures host_collect
        # drives), under an `if` (cadence-gated work — eval every N),
        # or in exclusive branch arms (mode selection, not a chain)
        # don't count: the finding is the straight-line gather/update
        # split one fused program would absorb.
        for loop in loops:
            body_calls: dict[str, ast.Call] = {}
            for sub in ast.walk(loop):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and not inside_traced_def(mod, sub, traced)
                    and not _gated_in_loop(mod, sub, loop)
                ):
                    bindings = bindings_for(sub)
                    if sub.func.id in bindings:
                        body_calls.setdefault(sub.func.id, sub)
            chain = [
                c
                for c in body_calls.values()
                if not any(
                    mod.exclusive_branches(c, o)
                    for o in body_calls.values()
                    if o is not c
                )
            ]
            if len(chain) >= 2:
                chain.sort(key=lambda c: (c.lineno, c.col_offset))
                first = chain[0]
                names = sorted(c.func.id for c in chain)
                findings.append(
                    Finding(
                        DISPATCH_GRANULARITY, mod.relpath,
                        first.lineno, first.col_offset,
                        f"step loop dispatches {len(names)} distinct "
                        f"compiled programs per iteration "
                        f"({', '.join(f'`{n}`' for n in names)}) — "
                        "the gather/update split the device plane "
                        "fuses into ONE program (ppo.make_device_"
                        "update_step's shape); fuse them or suppress "
                        "with the reason the split is load-bearing",
                        mod.enclosing_function(first),
                    )
                )
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings
