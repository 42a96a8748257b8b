"""``python -m repro.staticcheck`` — cache-coherence & trace-discipline
static checker over the runtime source itself.

PR 7's analyzer checks the *configuration* (policies, footprints, mined
tables) and its sanitizer cross-checks the *running state* on sampled ticks.
What neither covers is the code: the event scheduler's whole performance
story rests on epoch-guarded caches, dirty-sets, and counter-group slack
staying coherent with dozens of hand-written mutation sites scattered across
``runtime.py``/``simulator.py``/``memo.py`` — a future edit that writes
guarded state without the matching invalidation only trips the sanitizer
*probabilistically, at runtime*.  This module lifts those contracts to an
AST-level static guarantee, checked in CI on every push:

  C1-mutation   mutation coverage: a declared registry of guarded state
                (``MUTATION_RULES``) and its invalidation idioms (epoch
                bump / ``_mark_dirty``, counter-cache clear, heap push /
                lazy-invalidate, ``bump_if_live``).  Every function that
                writes a guarded attribute — directly, through a mutator
                method (``.append``/``.pop``/…), or through a local alias
                of the guarded container — must hit a matching invalidation
                on ALL control-flow paths (intra-procedural flow over
                (wrote, invalidated) states; known dirtying-transition
                methods count as invalidators).  Pair-grouped fields
                (``NodeRun.*_cache``/``*_epoch``) must be written together.
  C2-trace      trace discipline: inside ``jax.jit``-decorated functions,
                ``lax`` loop/branch bodies, and Pallas kernels, flag
                host-sync coercions (``float()``/``int()``/``bool()``/
                ``.item()``/``.tolist()`` on traced values), ``np.`` calls
                applied to traced arguments, and Python ``if``/``while`` on
                traced scalars.  ``static_argnames`` params are untainted;
                ``.shape``/``.ndim``/``.dtype``/``len()`` launder taint
                (they are static under tracing).
  C3-compat     compat-bypass: direct ``Mesh``/``shard_map``/``pltpu``
                compiler-param usage outside ``repro/compat.py`` (the
                one layer where those calls live).
  C4-dispatch   dispatch-shape discipline: ``pack_beam`` calls whose k
                argument doesn't flow through ``bucket_k`` (or the
                ``k_max`` cap it buckets to), and calls into the jitted
                entrypoints ``admit_beam``/``score_beam`` outside their
                blessed wrappers (``fused_admit``/``Scorer.score``) — the
                bounded-compile-shape invariant.

Approximations (deliberate, documented so findings stay interpretable):
the C1 flow treats loop bodies as executing once (every registry idiom
invalidates unconditionally; "invalidate only inside a maybe-empty loop"
is accepted), and C2 scans only *directly* traced scopes — helpers like
``static_gain_terms`` that branch on static params are called from jit but
are legitimately bimodal host/device code.

Zero findings are required on the default tree.  A site that is safe for
reasons the checker cannot see is listed in ``BASELINE`` with a written
justification; baselined hits land in ``report.meta["baselined"]``, never
in the findings.

Reuses :class:`repro.core.analysis.Finding`/``AnalysisReport``.  Exit
status mirrors ``python -m repro.analysis``: 0 clean, 1 findings, 2 under
``--strict`` when any finding is an error.
"""
from __future__ import annotations

import argparse
import ast
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from repro.core.analysis import AnalysisReport, exit_code

# ======================================================================
# registries
# ======================================================================

#: container-mutating method names that count as writes to the object the
#: method chain hangs off (``self._read_index.setdefault(nk, set()).add(k)``
#: is a write to ``_read_index``)
MUTATOR_METHODS: FrozenSet[str] = frozenset({
    "append", "extend", "insert", "pop", "popitem", "remove", "clear",
    "add", "discard", "update", "setdefault", "sort",
})


@dataclass(frozen=True)
class MutationRule:
    """One C1 entry: guarded attributes + the invalidation idiom that must
    accompany any write to them.

    ``invalidators`` entries are ``"call:<dotted tail>"`` (a call whose
    dotted name ends with the tail — ``_mark_dirty``,
    ``_demand_cache.clear``) or ``"write:<attr>"`` (a write to that attr is
    itself the invalidation — the heap's lazy ``_live`` tombstone).  A rule
    with ``pair_groups`` instead requires group members to be written
    together.  A rule with neither bans writes outright (single-writer
    fields); ``exempt`` qualnames are the sanctioned writers.
    """
    name: str
    modules: Tuple[str, ...]                  # relpath suffixes the rule scans
    attrs: FrozenSet[str]
    invalidators: Tuple[str, ...] = ()
    pair_groups: Tuple[FrozenSet[str], ...] = ()
    mutators: FrozenSet[str] = MUTATOR_METHODS
    exempt: FrozenSet[str] = frozenset()


MUTATION_RULES: Tuple[MutationRule, ...] = (
    # EpisodeState/NodeRun/HypRun fields the phase-4 rebuild caches hang off:
    # any write must bump the episode epoch + dirty-set (directly or through
    # a known dirtying-transition method, each of which marks internally).
    MutationRule(
        name="runtime-epoch",
        modules=("core/runtime.py",),
        attrs=frozenset({
            "history", "pending_action", "inflight", "hyp_runs", "phase",
            "warm_until", "matched_hr", "step_idx", "status", "result",
            "job", "served", "epoch",
        }),
        invalidators=(
            "call:_mark_dirty", "call:_mark_dirty_eid",
            # dirtying transitions: each calls _mark_dirty before mutating
            "call:_finish_action", "call:_commit_path", "call:_squash_one",
            "call:_squash_all", "call:_prune_beam", "call:_serve_spec",
        ),
        exempt=frozenset({"BPasteRuntime._mark_dirty"}),  # IS the bump
    ),
    # epoch-stamped cache pairs: writing the cache without the stamp (or
    # vice versa) silently serves a stale value next epoch check.
    MutationRule(
        name="noderun-pairs",
        modules=("core/runtime.py",),
        attrs=frozenset({
            "args_cache", "args_epoch", "mkey_cache", "mkey_epoch",
            "serv_epoch", "serv_pubs", "serv_inval", "serv_ok",
        }),
        pair_groups=(
            frozenset({"args_cache", "args_epoch"}),
            frozenset({"mkey_cache", "mkey_epoch"}),
            frozenset({"serv_epoch", "serv_pubs", "serv_inval", "serv_ok"}),
        ),
    ),
    # counter-group demand: any change to the running set or the group
    # counters must clear the O(#groups) demand cache.
    MutationRule(
        name="sim-demand",
        modules=("core/simulator.py",),
        attrs=frozenset({"running", "_groups"}),
        invalidators=("call:_demand_cache.clear",),
    ),
    # completion-time heap: a re-rated job needs a fresh heap entry
    # (_push) or a lazy tombstone (dropping its _live sequence number).
    MutationRule(
        name="sim-heap",
        modules=("core/simulator.py",),
        attrs=frozenset({"_rate"}),
        invalidators=("call:_push", "write:_live"),
    ),
    # job class flips corrupt the auth/spec counter split unless they go
    # through Simulator.set_speculative — ban every other write.
    MutationRule(
        name="class-flip",
        modules=("core/runtime.py", "core/simulator.py",
                 "core/model_service.py"),
        attrs=frozenset({"speculative", "priority"}),
        exempt=frozenset({"Simulator.set_speculative"}),
    ),
    # entry-table writes must keep the read index coherent.
    MutationRule(
        name="store-index",
        modules=("core/memo.py",),
        attrs=frozenset({"entries"}),
        invalidators=("call:_deindex", "write:_read_index"),
    ),
    # tool_pubs is the servability-cache monotone counter: single writer
    # (publish), never decremented — _deindex deliberately leaves it alone.
    MutationRule(
        name="store-pubs",
        modules=("core/memo.py",),
        attrs=frozenset({"tool_pubs"}),
        exempt=frozenset({"ResultStore.publish"}),
    ),
    # live-state tool writes must advance the sandbox staleness version.
    MutationRule(
        name="live-bump",
        modules=("core/executor.py",),
        attrs=frozenset({"M", "F", "E"}),
        invalidators=("call:bump_if_live",),
        mutators=frozenset({"set", "delete"}),
    ),
)

#: sites that are safe for reasons outside the intra-procedural view —
#: keyed (rule id, site), value is the justification recorded in
#: ``report.meta["baselined"]``.
BASELINE: Dict[Tuple[str, str], str] = {
    ("C1-mutation", "core/runtime.py:BPasteRuntime._launch_frontier"):
        "settle-warm flips a pending env_warmup prep to reused mid-walk; "
        "the mutating walk only runs while phase 4 is rebuilding a dirty "
        "episode's frontier (the value being cached), and the sanitizer "
        "uses the settle_warm=False variant, which never mutates",
    ("C1-mutation", "core/runtime.py:BPasteRuntime._refresh_beam"):
        "appends fresh HypRuns while phase 4 rebuilds a dirty episode's "
        "beam — the epoch-guarded caches are recomputed in the same pass, "
        "and new NodeRuns start at epoch -1 so nothing stale can serve",
}

# C4: blessed wrappers for the jitted entrypoints (relpath suffix, qualname)
_JIT_ENTRYPOINT_WRAPPERS: Dict[str, Tuple[Tuple[str, str], ...]] = {
    "admit_beam": (("core/admission.py", "fused_admit"),),
    "score_beam": (("core/scoring.py", "Scorer.score"),),
}

_LAX_LOOP_FUNCS = frozenset({
    "while_loop", "fori_loop", "scan", "cond", "switch", "map",
})
_TAINT_LAUNDER_ATTRS = frozenset({"shape", "ndim", "dtype"})
_JAX_NAMESPACES = frozenset({"jnp", "lax", "jax", "pl", "pltpu"})


# ======================================================================
# small AST helpers
# ======================================================================

def _dotted(node: ast.AST) -> str:
    """Dotted-name suffix of an Attribute/Name chain (``self._groups.get``);
    chains hanging off calls/subscripts keep only the attribute tail."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _walk_shallow(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk that does NOT descend into nested function/lambda bodies
    (those execute on their own schedule and are analyzed separately)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            stack.append(c)


def _chain_base_attr(node: ast.AST, attrs: FrozenSet[str],
                     aliases: Dict[str, str]) -> Optional[str]:
    """Descend an Attribute/Subscript/Call chain to the guarded attribute
    (or alias) it hangs off, if any."""
    while True:
        if isinstance(node, ast.Attribute):
            if node.attr in attrs:
                return node.attr
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Name):
            return aliases.get(node.id)
        else:
            return None


def _target_attr(t: ast.AST, attrs: FrozenSet[str],
                 aliases: Dict[str, str]) -> List[str]:
    """Guarded attrs written by one assignment/delete target."""
    if isinstance(t, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in t.elts:
            out.extend(_target_attr(e, attrs, aliases))
        return out
    if isinstance(t, ast.Starred):
        return _target_attr(t.value, attrs, aliases)
    if isinstance(t, ast.Attribute):
        return [t.attr] if t.attr in attrs else []
    if isinstance(t, ast.Subscript):
        a = _chain_base_attr(t.value, attrs, aliases)
        return [a] if a else []
    # plain Name rebinding is not a mutation of the guarded object
    return []


def _writes_in(stmt: ast.AST, attrs: FrozenSet[str],
               aliases: Dict[str, str],
               mutators: FrozenSet[str]) -> Set[str]:
    """Guarded attrs this statement writes: assignment targets, ``del``,
    and mutator-method calls anywhere in the statement."""
    written: Set[str] = set()
    for n in _walk_shallow(stmt):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                written.update(_target_attr(t, attrs, aliases))
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            written.update(_target_attr(n.target, attrs, aliases))
        elif isinstance(n, ast.Delete):
            for t in n.targets:
                written.update(_target_attr(t, attrs, aliases))
        elif (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
              and n.func.attr in mutators):
            a = _chain_base_attr(n.func.value, attrs, aliases)
            if a:
                written.add(a)
    return written


def _alias_source_attr(v: ast.AST, attrs: FrozenSet[str]) -> Optional[str]:
    """Does this RHS expression evaluate to (an element of) a guarded
    container, so the bound name aliases it?  Copies (``list(...)``,
    comprehensions, slices of copies) do NOT alias."""
    if isinstance(v, ast.Attribute) and v.attr in attrs:
        return v.attr
    if isinstance(v, ast.Subscript):
        inner = v.value
        if isinstance(inner, ast.Attribute) and inner.attr in attrs:
            return inner.attr
    if (isinstance(v, ast.Call) and isinstance(v.func, ast.Attribute)
            and v.func.attr in ("get", "setdefault")
            and isinstance(v.func.value, ast.Attribute)
            and v.func.value.attr in attrs):
        return v.func.value.attr
    return None


def _collect_aliases(fn: ast.AST, attrs: FrozenSet[str]) -> Dict[str, str]:
    """Local names bound to guarded containers (``g = self._groups.get(k)``,
    ``g = self._groups[k] = [...]``) — writes through them count."""
    aliases: Dict[str, str] = {}
    for n in _walk_shallow(fn):
        if not isinstance(n, ast.Assign):
            continue
        names = [t.id for t in n.targets if isinstance(t, ast.Name)]
        if not names:
            continue
        src = _alias_source_attr(n.value, attrs)
        if src is None:
            # multi-target: ``g = self._groups[key] = [...]``
            for t in n.targets:
                if isinstance(t, ast.Subscript):
                    inner = t.value
                    if isinstance(inner, ast.Attribute) and inner.attr in attrs:
                        src = inner.attr
                        break
        if src is not None:
            for name in names:
                aliases[name] = src
    return aliases


# ======================================================================
# C1 — mutation-coverage dataflow
# ======================================================================

# flow state: (wrote a guarded attr, hit an invalidator) — sets of these
# (≤4 members) flow through the function; a terminal (True, False) state is
# a path that mutated guarded state without invalidating.

class _C1Flow:
    def __init__(self, rule: MutationRule, aliases: Dict[str, str]):
        self.rule = rule
        self.aliases = aliases
        self.written: Set[str] = set()     # attrs written anywhere (detail)
        self._write_specs = frozenset(
            s.split(":", 1)[1] for s in rule.invalidators
            if s.startswith("write:"))
        self._call_specs = tuple(
            s.split(":", 1)[1] for s in rule.invalidators
            if s.startswith("call:"))

    def _invalidates(self, stmt: ast.AST) -> bool:
        if self._call_specs:
            for n in _walk_shallow(stmt):
                if isinstance(n, ast.Call):
                    d = _dotted(n.func)
                    if any(d == tail or d.endswith("." + tail)
                           for tail in self._call_specs):
                        return True
        if self._write_specs and _writes_in(
                stmt, self._write_specs, self.aliases, self.rule.mutators):
            return True
        return False

    def _apply(self, stmt: ast.AST,
               states: Set[Tuple[bool, bool]]) -> Set[Tuple[bool, bool]]:
        w = _writes_in(stmt, self.rule.attrs, self.aliases, self.rule.mutators)
        self.written.update(w)
        inv = self._invalidates(stmt)
        if not w and not inv:
            return states
        return {(ws or bool(w), vs or inv) for ws, vs in states}

    def run_block(self, stmts, states):
        """Returns (fallthrough states, return/raise states, break states).
        Loop bodies run exactly once (see module docstring)."""
        cur = set(states)
        exits: Set[Tuple[bool, bool]] = set()
        breaks: Set[Tuple[bool, bool]] = set()
        for stmt in stmts:
            if not cur:
                break                      # unreachable
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue                   # analyzed as its own function
            if isinstance(stmt, (ast.Return, ast.Raise)):
                exits |= self._apply(stmt, cur)
                cur = set()
            elif isinstance(stmt, (ast.Break, ast.Continue)):
                breaks |= cur
                cur = set()
            elif isinstance(stmt, ast.If):
                cur = self._apply(stmt.test, cur)
                b1, e1, br1 = self.run_block(stmt.body, cur)
                b2, e2, br2 = self.run_block(stmt.orelse, cur)
                cur = b1 | b2
                exits |= e1 | e2
                breaks |= br1 | br2
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                head = stmt.iter if isinstance(stmt, (ast.For, ast.AsyncFor)) \
                    else stmt.test
                cur = self._apply(head, cur)
                b, e, br = self.run_block(stmt.body, cur)
                cur = b | br               # this loop consumes its breaks
                exits |= e
                if stmt.orelse:
                    b2, e2, br2 = self.run_block(stmt.orelse, cur)
                    cur, exits, breaks = b2, exits | e2, breaks | br2
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    cur = self._apply(item.context_expr, cur)
                b, e, br = self.run_block(stmt.body, cur)
                cur, exits, breaks = b, exits | e, breaks | br
            elif isinstance(stmt, ast.Try):
                b, e, br = self.run_block(stmt.body, cur)
                exits |= e
                breaks |= br
                out = set(b)
                for h in stmt.handlers:
                    hb, he, hbr = self.run_block(h.body, cur | b)
                    out |= hb
                    exits |= he
                    breaks |= hbr
                if stmt.orelse:
                    ob, oe, obr = self.run_block(stmt.orelse, b)
                    out = (out - b) | ob
                    exits |= oe
                    breaks |= obr
                if stmt.finalbody:
                    out, fe, fbr = self.run_block(stmt.finalbody, out)
                    exits |= fe
                    breaks |= fbr
                cur = out
            else:
                cur = self._apply(stmt, cur)
        return cur, exits, breaks


def _check_mutation_rule(rule: MutationRule, relpath: str,
                         functions: List[Tuple[str, ast.AST]],
                         report: AnalysisReport) -> None:
    for qualname, fn in functions:
        if qualname.split(".")[-1] == "__init__":
            continue                       # construction populates, by design
        if any(qualname == ex or qualname.endswith("." + ex)
               for ex in rule.exempt):
            continue
        site = f"{relpath}:{qualname}"
        aliases = _collect_aliases(fn, rule.attrs)
        if rule.pair_groups:
            written = set()
            for stmt in fn.body:
                written |= _writes_in(stmt, rule.attrs, aliases, rule.mutators)
            for group in rule.pair_groups:
                hit = written & group
                if hit and hit != group:
                    _emit(report, "C1-mutation", "error", site,
                          f"[{rule.name}] writes {sorted(hit)} without the "
                          f"rest of the cache/epoch group "
                          f"{sorted(group - hit)} — a stale pair serves "
                          f"under the next epoch check")
            continue
        flow = _C1Flow(rule, aliases)
        out, exits, breaks = flow.run_block(fn.body, {(False, False)})
        final = out | exits | breaks
        if not rule.invalidators:
            if flow.written:
                _emit(report, "C1-mutation", "error", site,
                      f"[{rule.name}] writes single-writer field(s) "
                      f"{sorted(flow.written)} outside the sanctioned "
                      f"writer(s) {sorted(rule.exempt) or '(none)'}")
            continue
        if any(w and not inv for w, inv in final):
            _emit(report, "C1-mutation", "error", site,
                  f"[{rule.name}] writes guarded state "
                  f"{sorted(flow.written)} but some path reaches the end "
                  f"of the function without any of "
                  f"{list(rule.invalidators)}")


# ======================================================================
# C2 — trace discipline
# ======================================================================

def _static_argnames(dec: ast.Call) -> Set[str]:
    names: Set[str] = set()
    for kw in dec.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
    return names


def _param_names(fn) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


def _positional_params(fn) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _traced_scopes(tree: ast.Module):
    """(fn node, tainted param names, kind) for every directly-traced scope:
    jit-decorated defs, local defs/lambdas handed to lax control flow,
    ``jax.jit(f)`` call-form targets, and Pallas kernel bodies."""
    by_name: Dict[str, ast.AST] = {}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(n.name, n)
    scopes: List[Tuple[ast.AST, Set[str], str]] = []
    seen: Set[int] = set()

    def add(fn, static: Set[str], kind: str, pos_only: bool = False):
        if fn is None or id(fn) in seen:
            return
        seen.add(id(fn))
        # pos_only (Pallas kernels): refs arrive positionally, so
        # keyword-only params are always static Python configuration
        names = _positional_params(fn) if pos_only else _param_names(fn)
        scopes.append((fn, set(names) - static, kind))

    def resolve(node):
        """Function-typed argument -> (def/lambda node, statically-bound
        param names) — ``functools.partial`` bindings are trace-time
        constants, not traced operands."""
        if isinstance(node, ast.Lambda):
            return node, set()
        if isinstance(node, ast.Name):
            fn = by_name.get(node.id)
            return (fn, set()) if fn is not None else None
        if (isinstance(node, ast.Call) and node.args
                and _dotted(node.func).endswith("partial")):
            r = resolve(node.args[0])
            if r is None:
                return None
            fn, bound = r
            bound = set(bound)
            bound.update(kw.arg for kw in node.keywords if kw.arg)
            if not isinstance(fn, ast.Lambda):
                bound.update(_positional_params(fn)[:len(node.args) - 1])
            return fn, bound
        return None

    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in n.decorator_list:
                d = _dotted(dec if not isinstance(dec, ast.Call) else dec.func)
                if d.endswith("jit") and not isinstance(dec, ast.Call):
                    add(n, set(), "jit")
                elif isinstance(dec, ast.Call) and d.endswith("partial"):
                    if any(_dotted(a).endswith("jit") for a in dec.args):
                        add(n, _static_argnames(dec), "jit")
        elif isinstance(n, ast.Call):
            d = _dotted(n.func)
            tail = d.split(".")[-1]
            # require the lax namespace explicitly: jax.tree.map and
            # friends are host-side maps, not traced control flow
            if tail in _LAX_LOOP_FUNCS and d.endswith("lax." + tail):
                for a in n.args:
                    r = resolve(a)
                    if r is not None:
                        add(r[0], r[1], f"lax.{tail} body")
            elif tail == "jit" and n.args:
                r = resolve(n.args[0])
                if r is not None:
                    add(r[0], r[1] | _static_argnames(n), "jit")
            elif tail == "pallas_call" and n.args:
                r = resolve(n.args[0])
                if r is not None:
                    add(r[0], r[1], "pallas kernel", pos_only=True)
    return scopes


def _taint_evidence(node: ast.AST, tainted: Set[str]) -> bool:
    """Does this expression observe a traced value?  ``.shape``/``.ndim``/
    ``.dtype``/``len()`` are static under tracing and launder taint."""
    if isinstance(node, ast.Attribute) and node.attr in _TAINT_LAUNDER_ATTRS:
        return False
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "len"):
        return False
    if isinstance(node, ast.Name):
        return node.id in tainted
    return any(_taint_evidence(c, tainted)
               for c in ast.iter_child_nodes(node))


def _mentions_jax(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Name) and n.id in _JAX_NAMESPACES
               for n in ast.walk(node))


def _scan_traced(fn, tainted: Set[str], kind: str, relpath: str,
                 qualname: str, report: AnalysisReport,
                 seen: Set[Tuple[str, int, str]]) -> None:
    site = f"{relpath}:{qualname}"

    def taint_targets(t):
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                taint_targets(e)
        elif isinstance(t, ast.Starred):
            taint_targets(t.value)
        elif isinstance(t, ast.Name):
            tainted.add(t.id)

    def flag(lineno: int, what: str):
        key = (site, lineno, what)
        if key in seen:
            return                         # two-pass scan revisits lines
        seen.add(key)
        _emit(report, "C2-trace", "error", site,
              f"[{kind}] line {lineno}: {what}")

    def scan_expr(e: ast.AST):
        for n in _walk_shallow(e):
            if not isinstance(n, ast.Call):
                continue
            d = _dotted(n.func)
            argv = list(n.args) + [kw.value for kw in n.keywords]
            if (isinstance(n.func, ast.Name)
                    and n.func.id in ("float", "int", "bool")
                    and any(_taint_evidence(a, tainted) for a in argv)):
                flag(n.lineno, f"host-sync coercion {n.func.id}() on a "
                               f"traced value")
            elif (isinstance(n.func, ast.Attribute)
                  and n.func.attr in ("item", "tolist")
                  and _taint_evidence(n.func.value, tainted)):
                flag(n.lineno, f".{n.func.attr}() forces a device sync on a "
                               f"traced value")
            elif (d.startswith("np.")
                  and any(_taint_evidence(a, tainted) for a in argv)):
                flag(n.lineno, f"numpy call {d}() on a traced argument "
                               f"(falls off the trace; use jnp)")

    def walk_body(stmts):
        # two passes so taints assigned later in loops still propagate
        for _ in range(2):
            for stmt in stmts:
                visit(stmt)

    def visit(stmt):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs inside a traced scope trace too (pl.when bodies,
            # scan carriers): inherit the closure taint + own params
            inner = set(tainted) | set(_param_names(stmt))
            _scan_traced(stmt, inner, kind, relpath,
                         f"{qualname}.<locals>.{stmt.name}", report, seen)
            return
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            val = stmt.value
            if val is not None:
                scan_expr(val)
                if _taint_evidence(val, tainted) or _mentions_jax(val):
                    targets = (stmt.targets if isinstance(stmt, ast.Assign)
                               else [stmt.target])
                    for t in targets:
                        taint_targets(t)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            scan_expr(stmt.test)
            if _taint_evidence(stmt.test, tainted):
                flag(stmt.lineno,
                     "Python branch on a traced value (concretizes the "
                     "tracer; use lax.cond/jnp.where)")
            walk_body(stmt.body)
            walk_body(stmt.orelse)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            scan_expr(stmt.iter)
            if _taint_evidence(stmt.iter, tainted):
                flag(stmt.lineno, "Python loop over a traced value")
                taint_targets(stmt.target)
            walk_body(stmt.body)
            walk_body(stmt.orelse)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                scan_expr(item.context_expr)
            walk_body(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            walk_body(stmt.body)
            for h in stmt.handlers:
                walk_body(h.body)
            walk_body(stmt.orelse)
            walk_body(stmt.finalbody)
            return
        scan_expr(stmt)

    walk_body(fn.body)


def _check_trace(relpath: str, tree: ast.Module,
                 qualnames: Dict[int, str],
                 report: AnalysisReport) -> None:
    seen: Set[Tuple[str, int, str]] = set()
    for fn, tainted, kind in _traced_scopes(tree):
        qn = qualnames.get(id(fn), getattr(fn, "name", "<lambda>"))
        if isinstance(fn, ast.Lambda):
            body = ast.Expr(value=fn.body)
            ast.copy_location(body, fn.body)
            wrapper = ast.FunctionDef(
                name="<lambda>", args=fn.args, body=[body],
                decorator_list=[], returns=None, type_comment=None)
            ast.copy_location(wrapper, fn)
            _scan_traced(wrapper, set(tainted), kind, relpath, qn, report,
                         seen)
        else:
            _scan_traced(fn, set(tainted), kind, relpath, qn, report, seen)


# ======================================================================
# C3 — compat bypass
# ======================================================================

def _check_compat(relpath: str, tree: ast.Module,
                  report: AnalysisReport) -> None:
    if relpath.endswith("compat.py"):
        return
    site = f"{relpath}"
    flagged: Set[Tuple[int, str]] = set()

    def flag(lineno: int, what: str):
        if (lineno, what) in flagged:
            return
        flagged.add((lineno, what))
        _emit(report, "C3-compat", "error", site,
              f"line {lineno}: {what} — route through repro.compat "
              f"(the one mesh/shard_map/Pallas-params layer)")

    for n in ast.walk(tree):
        if isinstance(n, ast.ImportFrom):
            mod = n.module or ""
            if mod == "jax.experimental.shard_map":
                flag(n.lineno, "direct jax.experimental.shard_map import")
            elif mod == "jax.sharding" and any(
                    a.name == "Mesh" for a in n.names):
                flag(n.lineno, "direct jax.sharding.Mesh import")
            elif mod == "jax" and any(
                    a.name in ("shard_map", "make_mesh") for a in n.names):
                flag(n.lineno, f"direct jax.{n.names[0].name} import")
        elif isinstance(n, ast.Attribute):
            d = _dotted(n)
            if n.attr in ("TPUCompilerParams", "CompilerParams") \
                    and "pltpu" in d.split("."):
                flag(n.lineno, f"direct {d} compiler-params construction")
            elif d in ("jax.shard_map", "jax.make_mesh",
                       "jax.sharding.Mesh", "jax.experimental.shard_map"):
                flag(n.lineno, f"direct {d} usage")


# ======================================================================
# C4 — dispatch-shape discipline
# ======================================================================

def _check_dispatch(relpath: str, tree: ast.Module,
                    qualnames: Dict[int, str],
                    report: AnalysisReport) -> None:
    # enclosing-function map for every call node
    encl: Dict[int, str] = {}

    def assign_encl(fn, qn):
        for n in _walk_shallow(fn):
            encl[id(n)] = qn

    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            assign_encl(n, qualnames.get(id(n), n.name))

    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        tail = _dotted(n.func).split(".")[-1]
        qn = encl.get(id(n), "<module>")
        site = f"{relpath}:{qn}"
        if tail == "pack_beam":
            k_arg = None
            if len(n.args) >= 2:
                k_arg = n.args[1]
            else:
                for kw in n.keywords:
                    if kw.arg == "k_max":
                        k_arg = kw.value
            if k_arg is None:
                continue
            ok = False
            for sub in ast.walk(k_arg):
                if isinstance(sub, ast.Call) \
                        and _dotted(sub.func).split(".")[-1] == "bucket_k":
                    ok = True
                elif (isinstance(sub, ast.Name) and sub.id == "k_max") or \
                        (isinstance(sub, ast.Attribute)
                         and sub.attr == "k_max"):
                    ok = True
            if not ok and isinstance(k_arg, ast.Name):
                # local assigned from bucket_k(...) earlier in the function
                for fn_node in ast.walk(tree):
                    if isinstance(fn_node, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)) \
                            and qualnames.get(id(fn_node)) == qn:
                        for a in _walk_shallow(fn_node):
                            if (isinstance(a, ast.Assign)
                                    and isinstance(a.value, ast.Call)
                                    and _dotted(a.value.func).split(".")[-1]
                                    == "bucket_k"
                                    and any(isinstance(t, ast.Name)
                                            and t.id == k_arg.id
                                            for t in a.targets)):
                                ok = True
            if not ok:
                _emit(report, "C4-dispatch", "error", site,
                      f"line {n.lineno}: pack_beam k argument does not flow "
                      f"through bucket_k/k_max — unbounded compile shapes "
                      f"for the jitted kernels downstream")
        elif tail in _JIT_ENTRYPOINT_WRAPPERS:
            allowed = _JIT_ENTRYPOINT_WRAPPERS[tail]
            if not any(relpath.endswith(mod)
                       and (qn == f or qn.endswith("." + f))
                       for mod, f in allowed):
                _emit(report, "C4-dispatch", "error", site,
                      f"line {n.lineno}: direct call into jitted entrypoint "
                      f"{tail}() outside its blessed wrapper "
                      f"{[f'{m}:{f}' for m, f in allowed]} — bypasses "
                      f"bucketing and shape discipline")


# ======================================================================
# driver
# ======================================================================

def _emit(report: AnalysisReport, rule: str, severity: str, site: str,
          detail: str) -> None:
    just = BASELINE.get((rule, site))
    if just is not None:
        report.meta.setdefault("baselined", []).append(
            {"rule": rule, "site": site, "detail": detail,
             "justification": just})
        return
    report.add(rule, severity, site, detail)


def _index_functions(tree: ast.Module):
    """[(qualname, node)] for every def, plus an id->qualname map."""
    out: List[Tuple[str, ast.AST]] = []
    qualnames: Dict[int, str] = {}

    def walk(node, prefix):
        for c in ast.iter_child_nodes(node):
            if isinstance(c, ast.ClassDef):
                walk(c, f"{prefix}{c.name}.")
            elif isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}{c.name}"
                out.append((qn, c))
                qualnames[id(c)] = qn
                walk(c, f"{qn}.<locals>.")
            else:
                walk(c, prefix)

    walk(tree, "")
    return out, qualnames


def check_source(src: str, relpath: str,
                 report: Optional[AnalysisReport] = None) -> AnalysisReport:
    """Run C1–C4 over one module's source text (tests feed snippets here
    with a crafted ``relpath`` to select the rule scope)."""
    if report is None:
        report = AnalysisReport()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        report.add("C0-syntax", "error", relpath, str(e))
        return report
    functions, qualnames = _index_functions(tree)
    for rule in MUTATION_RULES:
        if any(relpath.endswith(m) for m in rule.modules):
            _check_mutation_rule(rule, relpath, functions, report)
    _check_trace(relpath, tree, qualnames, report)
    _check_compat(relpath, tree, report)
    _check_dispatch(relpath, tree, qualnames, report)
    return report


def check_tree(root: Optional[Path] = None) -> AnalysisReport:
    """Run the checker over every module under ``src/repro``."""
    if root is None:
        root = Path(__file__).resolve().parent
    report = AnalysisReport()
    files = sorted(p for p in root.rglob("*.py")
                   if "__pycache__" not in p.parts)
    for p in files:
        relpath = p.relative_to(root).as_posix()
        check_source(p.read_text(), relpath, report)
    report.meta["files_checked"] = len(files)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.staticcheck",
        description="Cache-coherence & trace-discipline static checker "
                    "(rules C1-C4) over the runtime source.")
    ap.add_argument("--root", default=None,
                    help="package root to scan (default: the installed "
                         "repro package directory)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the report as JSON ('-' for stdout)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 2 when any finding is an error")
    args = ap.parse_args(argv)

    report = check_tree(Path(args.root) if args.root else None)
    print(report.render())
    base = report.meta.get("baselined", [])
    print(f"({report.meta.get('files_checked', 0)} files checked, "
          f"{len(base)} baselined site(s))")
    for b in base:
        print(f"  baselined {b['rule']} @ {b['site']}: {b['justification']}")
    if args.json:
        payload = json.dumps(report.to_json(), indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as fh:
                fh.write(payload + "\n")
    return exit_code(report, strict=args.strict)


if __name__ == "__main__":
    sys.exit(main())
