"""Framework core: findings, suppressions, the baseline ratchet, the
runner, and the static readers that give every rule one source of truth.

Nothing in here (or in any rule) imports the code under analysis — the
registries a rule needs are lifted out of their defining modules with
``ast`` (:meth:`RepoContext.static_literal`), so ``kfac-lint`` runs on a
bare stdlib Python and cannot be broken by an import-time bug in the
tree it is linting.
"""

import ast
import dataclasses
import json
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: the files the default run scans, relative to the repo root. Tests are
#: deliberately out: they monkeypatch, fake preconditioners and read
#: scratch env vars by design; the contracts below bind the shipped
#: tree. (A rule further narrows this through its ``scope``.)
DEFAULT_ROOTS = ('kfac_pytorch_tpu', 'examples', 'scripts')

#: suppression comment grammar::
#:
#:     x = 1  # kfac-lint: disable=rule-id[,rule-id] [-- reason]
#:
#: on the flagged line or the line directly above it; or, anywhere in a
#: file, ``# kfac-lint: disable-file=rule-id[,rule-id] [-- reason]`` to
#: waive the rule for the whole file. The reason is free text for the
#: reviewer; the linter only parses the ids.
_SUPPRESS_RE = re.compile(
    r'#\s*kfac-lint:\s*(disable(?:-file)?)=([\w,-]+)')


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation. ``key`` (see :func:`finding_key`) is what the
    baseline pins — it hangs off the *content* of the flagged line, not
    its number, so unrelated edits above it don't churn the baseline."""
    rule: str
    path: str            # repo-relative, posix separators
    line: int            # 1-indexed
    message: str
    col: int = 0

    def render(self) -> str:
        return f'{self.path}:{self.line}:{self.col} [{self.rule}] {self.message}'


def finding_key(f: Finding, line_text: str) -> str:
    norm = ' '.join(line_text.split())
    return f'{f.rule}:{f.path}:{norm}'


class ModuleInfo:
    """A parsed source file plus everything rules repeatedly need."""

    def __init__(self, root: str, relpath: str):
        self.root = root
        self.relpath = relpath.replace(os.sep, '/')
        self.abspath = os.path.join(root, relpath)
        with open(self.abspath, encoding='utf-8') as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree: Optional[ast.AST] = ast.parse(self.text,
                                                     filename=self.relpath)
        except SyntaxError as e:          # pragma: no cover - repo parses
            self.tree = None
            self.parse_error = e
        self._suppressed = self._scan_suppressions()

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ''

    def _scan_suppressions(self):
        per_line: Dict[int, set] = {}
        whole_file: set = set()
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            ids = {r for r in m.group(2).split(',') if r}
            if m.group(1) == 'disable-file':
                whole_file |= ids
            else:
                per_line.setdefault(i, set()).update(ids)
        return per_line, whole_file

    def is_suppressed(self, rule_id: str, lineno: int) -> bool:
        per_line, whole_file = self._suppressed
        if rule_id in whole_file:
            return True
        for ln in (lineno, lineno - 1):
            if rule_id in per_line.get(ln, set()):
                return True
        return False


class RepoContext:
    """Shared per-run state: the repo root, the module cache, and the
    statically-read registries (one source of truth, zero imports)."""

    def __init__(self, root: str):
        self.root = root
        self._modules: Dict[str, ModuleInfo] = {}
        self._literals: Dict[Tuple[str, str], object] = {}

    def module(self, relpath: str) -> ModuleInfo:
        relpath = relpath.replace(os.sep, '/')
        if relpath not in self._modules:
            self._modules[relpath] = ModuleInfo(self.root, relpath)
        return self._modules[relpath]

    def static_literal(self, relpath: str, name: str):
        """The literal value of a module-level ``NAME = <literal>``
        assignment in ``relpath``, evaluated without importing it.
        Handles plain literals, tuples/lists/dicts/sets of literals,
        and ``frozenset({...})``. Raises ``KeyError`` if absent."""
        cache_key = (relpath, name)
        if cache_key in self._literals:
            return self._literals[cache_key]
        tree = self.module(relpath).tree
        if tree is None:
            raise KeyError(f'{relpath} failed to parse')
        for node in tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = [t.id for t in node.targets
                           if isinstance(t, ast.Name)]
                value = node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None \
                    and isinstance(node.target, ast.Name):
                targets, value = [node.target.id], node.value
            else:
                continue
            if name not in targets:
                continue
            if (isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Name)
                    and value.func.id == 'frozenset' and value.args):
                value = value.args[0]
            try:
                lit = ast.literal_eval(value)
            except ValueError:
                raise KeyError(
                    f'{relpath}:{name} is not a static literal') from None
            self._literals[cache_key] = lit
            return lit
        raise KeyError(f'no module-level {name} in {relpath}')


class Rule:
    """Base class. Subclasses set ``id``/``summary``/``invariant``/
    ``caught`` (the README table columns) and implement ``check``."""

    id: str = ''
    summary: str = ''
    #: the project invariant this rule encodes (README table)
    invariant: str = ''
    #: which past PR's review-round bug it would have caught (README table)
    caught: str = ''

    def scope(self, relpath: str) -> bool:
        """Whether this rule looks at ``relpath`` at all."""
        return True

    def check(self, mod: ModuleInfo, ctx: RepoContext) -> List[Finding]:
        raise NotImplementedError


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # new (not baselined, not suppressed)
    baselined: List[Finding]
    stale_baseline: List[str]        # baseline keys no finding matched
    suppressed: int
    files_scanned: int
    rules_run: Tuple[str, ...]

    @property
    def failed(self) -> bool:
        return bool(self.findings or self.stale_baseline)

    def to_json(self) -> dict:
        return {
            'version': 1,
            'failed': self.failed,
            'files_scanned': self.files_scanned,
            'rules_run': list(self.rules_run),
            'suppressed': self.suppressed,
            'findings': [dataclasses.asdict(f) for f in self.findings],
            'baselined': [dataclasses.asdict(f) for f in self.baselined],
            'stale_baseline': list(self.stale_baseline),
        }


def discover_files(root: str, roots: Sequence[str] = DEFAULT_ROOTS
                   ) -> List[str]:
    out = []
    for entry in roots:
        top = os.path.join(root, entry)
        if os.path.isfile(top) and entry.endswith('.py'):
            out.append(entry)
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ('__pycache__', '.git'))
            for fn in sorted(filenames):
                if fn.endswith('.py'):
                    rel = os.path.relpath(os.path.join(dirpath, fn), root)
                    out.append(rel.replace(os.sep, '/'))
    return sorted(out)


def load_baseline(path: str) -> Dict[str, str]:
    """``lint-baseline.json``: finding key -> written justification.
    Every entry MUST carry a non-empty justification — an unexplained
    baseline entry is itself a lint error (enforced in run_lint)."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding='utf-8') as f:
        doc = json.load(f)
    entries = doc.get('entries', doc) if isinstance(doc, dict) else {}
    return {str(k): str(v) for k, v in entries.items()}


def write_baseline(path: str, entries: Dict[str, str]) -> None:
    doc = {
        '_comment': (
            'kfac-lint ratchet: accepted pre-existing findings, each '
            'with a justification. New findings never land here '
            'silently (the CI gate fails); fixed findings make their '
            'entry stale, which also fails until it is deleted.'),
        'entries': dict(sorted(entries.items())),
    }
    with open(path, 'w', encoding='utf-8') as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write('\n')


def run_lint(root: str,
             rules: Sequence[Rule],
             rule_ids: Optional[Sequence[str]] = None,
             roots: Sequence[str] = DEFAULT_ROOTS,
             baseline: Optional[Dict[str, str]] = None,
             collect: Optional[Callable[[Finding], None]] = None
             ) -> LintResult:
    """Run ``rules`` (optionally filtered to ``rule_ids``) over the
    repo at ``root`` and fold in suppressions and the baseline."""
    active = [r for r in rules
              if rule_ids is None or r.id in set(rule_ids)]
    if rule_ids is not None:
        known = {r.id for r in rules}
        unknown = sorted(set(rule_ids) - known)
        if unknown:
            raise KeyError(f'unknown rule id(s) {unknown}; '
                           f'known: {sorted(known)}')
    ctx = RepoContext(root)
    files = discover_files(root, roots)
    raw: List[Tuple[Finding, str]] = []   # (finding, flagged line text)
    suppressed = 0
    for rel in files:
        mod = ctx.module(rel)
        if mod.parse_error is not None:   # pragma: no cover - repo parses
            raw.append((Finding('parse', rel, mod.parse_error.lineno or 0,
                                f'syntax error: {mod.parse_error.msg}'), ''))
            continue
        for rule in active:
            if not rule.scope(rel):
                continue
            for f in rule.check(mod, ctx):
                if mod.is_suppressed(f.rule, f.line):
                    suppressed += 1
                    continue
                if collect is not None:
                    collect(f)
                raw.append((f, mod.line_text(f.line)))
    baseline = dict(baseline or {})
    new: List[Finding] = []
    base: List[Finding] = []
    matched_keys = set()
    for f, line_text in raw:
        key = finding_key(f, line_text)
        if key in baseline:
            # the entry is not STALE either way — the site still exists;
            # what varies is whether the justification earns the waiver
            matched_keys.add(key)
            just = baseline[key].strip()
            if not just or just.upper().startswith('TODO'):
                new.append(dataclasses.replace(
                    f, message=f.message + ' [baselined without a '
                    'justification — write one or fix it]'))
                continue
            base.append(f)
        else:
            new.append(f)
    # stale = fixed-but-not-deleted, judged only for the rules that RAN:
    # a --rule-filtered run must not condemn entries it never re-checked
    active_ids = {r.id for r in active}
    stale = sorted(k for k in set(baseline) - matched_keys
                   if k.split(':', 1)[0] in active_ids)
    new.sort(key=lambda f: (f.path, f.line, f.rule))
    base.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings=new, baselined=base, stale_baseline=stale,
                      suppressed=suppressed, files_scanned=len(files),
                      rules_run=tuple(r.id for r in active))


def baseline_entries_for(result: LintResult, ctx_root: str,
                         justification: str = 'TODO: justify or fix'
                         ) -> Dict[str, str]:
    """Keys for ``--write-baseline``: every current finding, stamped
    with a placeholder justification the author must replace (an empty
    or TODO justification still fails the run — see run_lint)."""
    ctx = RepoContext(ctx_root)
    out = {}
    for f in result.findings + result.baselined:
        line_text = ctx.module(f.path).line_text(f.line)
        out[finding_key(f, line_text)] = justification
    return out
