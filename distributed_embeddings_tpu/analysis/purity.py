"""traced-purity pass: no banned host effects reachable from jit roots.

docs/design.md §15's honesty rule — "trace and stats can never
disagree" — depends on traced programs being pure: a ``journal()``, a
metrics update, a ``time.*`` read, a global-RNG draw or file I/O inside
a ``jax.jit``/``shard_map``-wrapped function executes ONCE at trace
time and then never again, so every retrace-sensitive cache hit makes
the side channel silently lie about what the device actually ran.

Roots: functions wrapped by ``jax.jit`` / ``pjit`` / ``shard_map``
(decorators, ``partial(jax.jit, ...)`` decorators, and call-form
``jax.jit(fn)`` where ``fn`` resolves lexically).  Reachability walks
the intra-repo call graph from each root.

``obs.trace`` HOST SPANS (``span``/``begin``/``end``/...) are on the
banned list: inside traced code a span would last as long as Python's
tracing did and say nothing of the device.  The sanctioned instrument
there is ``obs.trace.phase`` (``jax.named_scope``: metadata on the
operations, no host effect), so the walk never descends into
``obs.trace``; everything on the banned list is flagged at its call
site.

Rule: ``purity/host-effect-in-traced`` — symbol is
``<root>-><offending function>:<effect>`` so the id survives line
churn.
"""

from __future__ import annotations

import ast

from typing import Dict, List, Optional, Set, Tuple

from distributed_embeddings_tpu.analysis import core
from distributed_embeddings_tpu.analysis.core import Context, Finding

_JIT_WRAPPERS = frozenset({
    'jax.jit', 'jit', 'jax.pjit', 'pjit',
    'jax.experimental.pjit.pjit',
    'shard_map', 'jax.shard_map',
})
_TRACE_MOD = 'distributed_embeddings_tpu.obs.trace'

# banned host effects by fully qualified prefix (resolved through the
# module's import aliases)
_BANNED_PREFIXES: Tuple[Tuple[str, str], ...] = (
    ('distributed_embeddings_tpu.utils.resilience.journal', 'journal'),
    ('distributed_embeddings_tpu.obs.metrics.inc', 'metrics'),
    ('distributed_embeddings_tpu.obs.metrics.observe', 'metrics'),
    ('distributed_embeddings_tpu.obs.metrics.set_gauge', 'metrics'),
    ('distributed_embeddings_tpu.obs.metrics.journal_snapshot',
     'metrics'),
    ('distributed_embeddings_tpu.obs.trace.span', 'span'),
    ('distributed_embeddings_tpu.obs.trace.begin', 'span'),
    ('distributed_embeddings_tpu.obs.trace.end', 'span'),
    ('distributed_embeddings_tpu.obs.trace.complete', 'span'),
    ('distributed_embeddings_tpu.obs.trace.async_span', 'span'),
    ('distributed_embeddings_tpu.obs.trace.instant', 'span'),
    ('time.', 'time'),
    ('numpy.random.', 'global-rng'),
    ('random.', 'global-rng'),
    ('os.remove', 'file-io'), ('os.rename', 'file-io'),
    ('os.replace', 'file-io'), ('os.makedirs', 'file-io'),
    ('os.open', 'file-io'), ('shutil.', 'file-io'),
)


def _is_jit_wrapper(mod: core.Module, fn: ast.AST) -> bool:
  d = core.resolve_target(mod, fn) or core.dotted(fn)
  return d in _JIT_WRAPPERS


def _banned_effect(mod: core.Module, call: ast.Call) -> Optional[str]:
  fn = call.func
  if isinstance(fn, ast.Name) and fn.id == 'open' \
      and 'open' not in mod.aliases:
    return 'file-io:open'
  resolved = core.resolve_target(mod, fn)
  if resolved is None:
    return None
  for prefix, label in _BANNED_PREFIXES:
    if resolved == prefix or (prefix.endswith('.')
                              and resolved.startswith(prefix)):
      return f'{label}:{resolved}'
  return None


def _resolve_name_to_func(ctx: Context, mod: core.Module,
                          idx: core.FuncIndex, name: str, scope: str
                          ) -> Optional[Tuple[core.Module, str]]:
  parts = scope.split('.') if scope else []
  for k in range(len(parts), -1, -1):
    q = '.'.join(parts[:k] + [name])
    if q in idx.functions:
      return mod, q
  resolved = mod.aliases.get(name)
  if resolved:
    hit = ctx.module_for_target(resolved)
    if hit is not None and hit[1] and hit[1] in ctx.index(
        hit[0]).functions:
      return hit[0], hit[1]
  return None


def _callees(ctx: Context, mod: core.Module, idx: core.FuncIndex,
             fnode: ast.AST, scope: str
             ) -> Set[Tuple[str, str]]:
  out: Set[Tuple[str, str]] = set()
  cls = scope.split('.')[0] if scope else None
  for node in ast.walk(fnode):
    if not isinstance(node, ast.Call):
      continue
    fn = node.func
    hit: Optional[Tuple[core.Module, str]] = None
    if isinstance(fn, ast.Name):
      hit = _resolve_name_to_func(ctx, mod, idx, fn.id, scope)
    elif isinstance(fn, ast.Attribute):
      if isinstance(fn.value, ast.Name) and fn.value.id == 'self' \
          and cls and f'{cls}.{fn.attr}' in idx.functions:
        hit = (mod, f'{cls}.{fn.attr}')
      else:
        resolved = core.resolve_target(mod, fn)
        if resolved:
          mh = ctx.module_for_target(resolved)
          if mh is not None and mh[1] and mh[1] in ctx.index(
              mh[0]).functions:
            hit = (mh[0], mh[1])
    if hit is not None and hit[0].modname != _TRACE_MOD:
      out.add((hit[0].relpath, hit[1]))
  return out


@core.register_pass('purity')
def run(ctx: Context) -> List[Finding]:
  findings: List[Finding] = []
  # 1. per-function: direct banned effects + callees
  effects: Dict[Tuple[str, str], List[Tuple[str, int]]] = {}
  callees: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
  for mod in ctx.modules.values():
    idx = ctx.index(mod)
    for qual, fnode in idx.functions.items():
      fid = (mod.relpath, qual)
      effs = []
      for node in ast.walk(fnode):
        if isinstance(node, ast.Call):
          eff = _banned_effect(mod, node)
          if eff is not None:
            effs.append((eff, node.lineno))
      effects[fid] = effs
      callees[fid] = _callees(ctx, mod, idx, fnode, qual)

  # 2. roots: jit/shard_map-wrapped functions
  roots: List[Tuple[str, str, int]] = []  # (relpath, qualname, line)
  for mod in ctx.modules.values():
    idx = ctx.index(mod)
    for qual, fnode in idx.functions.items():
      for dec in getattr(fnode, 'decorator_list', []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _is_jit_wrapper(mod, target):
          roots.append((mod.relpath, qual, fnode.lineno))
        elif isinstance(dec, ast.Call) and (
            core.resolve_target(mod, dec.func) or '').endswith(
                'functools.partial') and dec.args \
            and _is_jit_wrapper(mod, dec.args[0]):
          roots.append((mod.relpath, qual, fnode.lineno))
    for node in ast.walk(mod.tree):
      if isinstance(node, ast.Call) and _is_jit_wrapper(mod, node.func) \
          and node.args:
        arg = node.args[0]
        scope = idx.enclosing(node)
        if isinstance(arg, ast.Name):
          hit = _resolve_name_to_func(ctx, mod, idx, arg.id, scope)
          if hit is not None:
            roots.append((hit[0].relpath, hit[1], node.lineno))
        elif isinstance(arg, ast.Lambda):
          # analyse the lambda body inline under a synthetic id
          fid = (mod.relpath, f'{scope or "<module>"}.<jit-lambda>')
          effs = []
          for sub in ast.walk(arg):
            if isinstance(sub, ast.Call):
              eff = _banned_effect(mod, sub)
              if eff is not None:
                effs.append((eff, sub.lineno))
          effects[fid] = effs
          callees[fid] = _callees(ctx, mod, idx, arg, scope)
          roots.append((mod.relpath, fid[1], node.lineno))

  # 3. reachability from each root; flag banned effects
  n_reach = 0
  for rel, rqual, rline in sorted(set(roots)):
    seen: Set[Tuple[str, str]] = set()
    frontier = [(rel, rqual)]
    while frontier:
      fid = frontier.pop()
      if fid in seen:
        continue
      seen.add(fid)
      for eff, line in effects.get(fid, ()):
        findings.append(Finding(
            rule='purity/host-effect-in-traced', path=fid[0],
            line=line,
            symbol=f'{rqual}->{fid[1]}:{eff}',
            message=f'{eff} reachable from traced root {rqual} '
            f'({rel}:{rline}) — host effects inside jit/shard_map run '
            'once at trace time and then lie forever (design §15); '
            'hoist it outside the traced function'))
      frontier.extend(callees.get(fid, ()))
    n_reach += len(seen)
  ctx.meta['purity'] = {'roots': len(set(roots)),
                        'reachable_functions': n_reach}
  # de-duplicate identical ids (same effect reachable via two roots
  # keeps distinct root-prefixed symbols; duplicates only arise from
  # repeated identical (root, fn, effect) triples)
  uniq: Dict[str, Finding] = {}
  for f in findings:
    uniq.setdefault(f.id, f)
  return list(uniq.values())
