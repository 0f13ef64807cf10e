"""Rule ``trace-purity``: code reachable under jit/shard_map stays pure.

A traced body that calls ``time.*``, unseeded ``random``/``np.random``,
``print``, reads ``os.environ`` or mutates a module global doesn't
fail — it silently bakes one trace-time value into the compiled
program (or spams every retrace), which is exactly the class of bug
that cost a review round when a health-guard helper once logged from
inside the traced step. The runtime has no guard for this; the trace
is the only witness. This rule makes it a review-time fact.

Traced set, computed statically:

- **seed**: every function in ``TRACED_MODULES`` (engine.py and
  health.py are traced-library modules by charter — their docstrings
  say "pure and traceable" and the step builder calls them under
  shard_map), plus any function the tree passes to / decorates with
  ``jax.jit`` / ``shard_map`` / ``pjit`` / ``jax.remat`` /
  ``jax.checkpoint``;
- **propagation**: a function called *by* a traced function is traced
  too — resolved by name within the module and through the module's
  import table across the package, to a fixpoint.

Host-side escape hatches (``jax.debug.*``, ``jax.pure_callback``,
``io_callback``) are naturally exempt: the callback fn is passed as a
value, not called, so propagation never enters it.
"""

import ast
import os
from typing import Dict, List, Set, Tuple

from kfac_pytorch_tpu.analysis import astutil
from kfac_pytorch_tpu.analysis.core import Finding, ModuleInfo, \
    RepoContext, Rule

#: modules whose every function is traced-context by charter
TRACED_MODULES = (
    'kfac_pytorch_tpu/engine.py',
    'kfac_pytorch_tpu/health.py',
)

_WRAPPERS = ('jit', 'shard_map', 'pjit', 'remat', 'checkpoint')

_PKG = 'kfac_pytorch_tpu'


def _is_wrapper(func_node: ast.AST) -> bool:
    d = astutil.dotted(func_node)
    if d is None:
        return False
    last = d.split('.')[-1]
    return last in _WRAPPERS and (d == last or d.startswith('jax.')
                                  or d.endswith('.' + last))


class _ModuleGraph:
    """Per-module function table + import table + call edges."""

    def __init__(self, relpath: str, mod: ModuleInfo, known: Set[str]):
        self.relpath = relpath
        self.funcs: Dict[str, ast.AST] = dict(astutil.func_defs(mod.tree))
        # simple-name -> qualnames defined in this module
        self.by_name: Dict[str, List[str]] = {}
        for qual in self.funcs:
            self.by_name.setdefault(qual.split('.')[-1], []).append(qual)
        self.imports = self._imports(mod.tree, known)

    def _imports(self, tree: ast.AST, known: Set[str]) -> Dict[str, str]:
        """alias -> package-relative module path ('a/b.py'), or
        'a/b.py::name' for a from-import of a single function."""
        out: Dict[str, str] = {}

        def rel_of(modname: str):
            if not modname.startswith(_PKG):
                return None
            p = modname.replace('.', '/') + '.py'
            if p in known:
                return p
            p = modname.replace('.', '/') + '/__init__.py'
            return p if p in known else None

        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    rel = rel_of(a.name)
                    if rel and a.asname:
                        out[a.asname] = rel
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = self.relpath
                    for _ in range(node.level):
                        base = os.path.dirname(base)
                    modname = (base.replace('/', '.')
                               + ('.' + node.module if node.module else ''))
                else:
                    modname = node.module or ''
                if not modname.startswith(_PKG):
                    continue
                for a in node.names:
                    # 'from pkg import engine' binds the module itself;
                    # 'from pkg.engine import f' binds one name from it
                    alias = a.asname or a.name
                    sub = rel_of(modname + '.' + a.name)
                    if sub:
                        out[alias] = sub
                    else:
                        here = rel_of(modname)
                        if here:
                            out[alias] = here + '::' + a.name
        return out


class TracePurityRule(Rule):
    id = 'trace-purity'
    summary = 'jit/shard_map-reachable code: no time/random/print/env/global'
    invariant = ('trace purity: functions reachable under jit/shard_map '
                 'never call time.*, unseeded random/np.random, print, '
                 'read os.environ or mutate module globals')
    caught = ('trace-time values silently baked into compiled programs '
              '(PR 1/4 review rounds on the health guard and cohort '
              'tables)')

    def scope(self, relpath: str) -> bool:
        return relpath.startswith('kfac_pytorch_tpu/') \
            and not relpath.startswith('kfac_pytorch_tpu/analysis/')

    # ------------------------------------------------------------------
    def _state(self, ctx: RepoContext) -> Dict[str, List[Finding]]:
        cached = getattr(ctx, '_trace_purity_findings', None)
        if cached is not None:
            return cached
        rels = [r for r in self._package_files(ctx.root)
                if self.scope(r)]
        known = set(self._package_files(ctx.root))
        graphs: Dict[str, _ModuleGraph] = {}
        for rel in rels:
            mod = ctx.module(rel)
            if mod.tree is not None:
                graphs[rel] = _ModuleGraph(rel, mod, known)

        traced: Set[Tuple[str, str]] = set()
        for rel in TRACED_MODULES:
            g = graphs.get(rel)
            if g:
                traced |= {(rel, q) for q in g.funcs}

        # wrapper-detected seeds: decorators and jit(f)/shard_map(f, ..)
        for rel, g in graphs.items():
            mod = ctx.module(rel)
            for qual, fn in g.funcs.items():
                for dec in getattr(fn, 'decorator_list', []):
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    if _is_wrapper(target):
                        traced.add((rel, qual))
            # `fn = functools.partial(one_step, ...)` then `jit(fn)`:
            # follow the partial alias to the real body
            partial_alias: Dict[str, str] = {}
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call) \
                        and astutil.dotted(node.value.func) in (
                            'functools.partial', 'partial') \
                        and node.value.args \
                        and isinstance(node.value.args[0], ast.Name):
                    partial_alias[node.targets[0].id] = \
                        node.value.args[0].id
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call) and _is_wrapper(node.func) \
                        and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Name):
                        name = partial_alias.get(arg.id, arg.id)
                        for q in g.by_name.get(name, []):
                            traced.add((rel, q))

        # propagate through call edges to a fixpoint
        edges = self._call_edges(graphs)
        work = list(traced)
        while work:
            cur = work.pop()
            for nxt in edges.get(cur, ()):
                if nxt not in traced:
                    traced.add(nxt)
                    work.append(nxt)

        findings: Dict[str, List[Finding]] = {}
        for rel, qual in sorted(traced):
            g = graphs[rel]
            fn = g.funcs[qual]
            for f in self._check_body(rel, qual, fn):
                findings.setdefault(rel, []).append(f)
        ctx._trace_purity_findings = findings
        return findings

    def _package_files(self, root: str) -> List[str]:
        out = []
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, _PKG)):
            dirnames[:] = [d for d in dirnames if d != '__pycache__']
            for fn in sorted(filenames):
                if fn.endswith('.py'):
                    rel = os.path.relpath(os.path.join(dirpath, fn), root)
                    out.append(rel.replace(os.sep, '/'))
        return sorted(out)

    def _call_edges(self, graphs: Dict[str, _ModuleGraph]):
        edges: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
        for rel, g in graphs.items():
            for qual, fn in g.funcs.items():
                tgt = edges.setdefault((rel, qual), set())
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    f = node.func
                    if isinstance(f, ast.Name):
                        imp = g.imports.get(f.id)
                        if imp and '::' in imp:
                            orel, oname = imp.split('::')
                            og = graphs.get(orel)
                            if og:
                                for q in og.by_name.get(oname, []):
                                    tgt.add((orel, q))
                        else:
                            for q in g.by_name.get(f.id, []):
                                tgt.add((rel, q))
                    elif isinstance(f, ast.Attribute):
                        base = astutil.dotted(f.value)
                        if base == 'self' or base is None:
                            for q in g.by_name.get(f.attr, []):
                                tgt.add((rel, q))
                        else:
                            imp = g.imports.get(base)
                            if imp and '::' not in imp:
                                og = graphs.get(imp)
                                if og:
                                    for q in og.by_name.get(f.attr, []):
                                        tgt.add((imp, q))
        return edges

    def _check_body(self, rel: str, qual: str, fn: ast.AST
                    ) -> List[Finding]:
        out = []

        def flag(node, what):
            out.append(Finding(
                self.id, rel, node.lineno,
                f'{qual}() is reachable under jit/shard_map but {what} '
                f'— a trace-time value/effect bakes into the compiled '
                f'program; hoist it to the host side or suppress with '
                f'a reason', node.col_offset))

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = astutil.dotted(node.func)
                if d is None:
                    continue
                if d.startswith('time.'):
                    flag(node, f'calls {d}()')
                elif d == 'print':
                    flag(node, 'calls print()')
                elif d == 'open':
                    flag(node, 'calls open()')
                elif d.startswith('random.') \
                        or d.startswith('np.random.') \
                        or d.startswith('numpy.random.'):
                    flag(node, f'calls unseeded {d}()')
            elif isinstance(node, ast.Attribute):
                if astutil.dotted(node) == 'os.environ':
                    flag(node, 'reads os.environ')
            elif isinstance(node, ast.Global):
                flag(node, f'mutates module global(s) '
                           f'{", ".join(node.names)}')
        return out

    # ------------------------------------------------------------------
    def check(self, mod: ModuleInfo, ctx: RepoContext) -> List[Finding]:
        return self._state(ctx).get(mod.relpath, [])
