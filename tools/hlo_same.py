"""Whether two compiled programs hold the same operations.

  python3 tools/hlo_same.py <parent.txt> <change.txt>

for two optimized HLO texts (``benchmarks/dev/aot_hybrid.py ... --hlo
<file>``, ``compiled.as_text()``).  What a change of metadata alone may
move is left out before they are compared: ``metadata={...}`` (the
phases, ``obs.trace.phase``), the tables of source files and stack
frames at the top, and the numbers in instruction and computation names
(``%fusion.12``, ``%region_3.45.clone``): XLA numbers them as it goes, so
they shift when an operation is bound in another place (a call inlined,
PR 35's ``routing.cumsum0``) though the program is the same; and the
source locations inside a Pallas kernel's serialized Mosaic module (the
``"body"`` of a ``tpu_custom_call``: each is parsed and stands in the
text as the digest of its assembly without locations).  Names are
renumbered inside each computation by first appearance, and the two
texts are equal when their computations are, as multisets.  Exit code 0
where they are, 1 with the computations only one side has otherwise.
"""
import base64
import collections
import functools
import hashlib
import re
import sys

_TABLE = ('FileNames', 'FunctionNames', 'FileLocations', 'StackFrames')
_METADATA = re.compile(r',? ?(?<!\w)metadata=\{[^}]*\}')
_NAME = re.compile(r'%[\w.\-]+|(?<=[( ])[A-Za-z_][\w.\-]*(?=: )')
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


@functools.lru_cache(maxsize=None)
def _kernel_digest(body):
  """A serialized Mosaic module as the digest of its assembly with no
  source location in it."""
  from jax._src.interpreters import mlir
  from jax._src.lib import tpu
  from jax._src.lib.mlir import ir
  context = mlir.make_ir_context()
  tpu.register_dialect(context)
  context.allow_unregistered_dialects = True    # ``stable_mosaic``
  with context:
    asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
        enable_debug_info=False)
  return hashlib.sha256(asm.encode()).hexdigest()[:16]


def computations(path):
  """The text's computations, each a list of lines, metadata stripped."""
  out, cur, table = [], [], False
  with open(path) as f:
    for line in f:
      if line.startswith(_TABLE):
        table = True
        continue
      if table and (re.match(r'^\d+ ', line) or not line.strip()):
        continue
      table = False
      if line.strip():
        line = _BODY.sub(lambda m: f'"body":"{_kernel_digest(m.group(1))}"',
                         line.rstrip())
        cur.append(_METADATA.sub('', line))
      elif cur:
        out.append(cur)
        cur = []
  return out + ([cur] if cur else [])


def canonical(lines):
  """One computation with its names renumbered by first appearance."""
  seen = {}

  def rename(m):
    name = m.group(0).lstrip('%')
    if name not in seen:
      base = re.sub(r'\.\d+(?=\.|$)|\.clone', '', name)
      base = re.sub(r'region_\d+', 'region', re.sub(r'\.+', '.', base))
      seen[name] = f'{base}#{len(seen)}'
    return seen[name]

  # a computation's parameters may be printed in either order
  index = lambda line: int(re.search(r' parameter\((\d+)\)', line).group(1))
  params = sorted((l for l in lines[1:] if ' parameter(' in l), key=index)
  rest = [l for l in lines[1:] if ' parameter(' not in l]
  return '\n'.join(_NAME.sub(rename, line)
                   for line in lines[:1] + params + rest)


def main(argv):
  first, second = (collections.Counter(map(canonical, computations(p)))
                   for p in argv[1:3])
  only = (first - second, second - first)
  print(f'{sum(first.values())} and {sum(second.values())} computations; '
        f'{sum(only[0].values())} only in the first, '
        f'{sum(only[1].values())} only in the second')
  for side, extra in zip(('first', 'second'), only):
    for text in list(extra)[:3]:
      print(f'--- only in the {side}:\n{text[:2000]}')
  return 1 if only[0] or only[1] else 0


if __name__ == '__main__':
  sys.exit(main(sys.argv))
