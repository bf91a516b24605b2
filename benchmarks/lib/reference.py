"""The plain reference for training cells, its control and its faults,
and the comparison that decides ``correct``.

The reference follows the first steps of a run from the seed alone: it
draws the same batches (the generator the mix names), computes the table
rows it needs from the seed (``lib/weights``), and trains them with
row-wise float32 arithmetic written out here.  It imports nothing of the
program and reads nothing the program made.  Only rows that a checked
batch touches are ever held, so it fits whatever the tables' size; a
table the configuration lists under ``head_reads_tables`` is held whole.

What belongs to a model class it finds by the names in the configuration
(``tables``, ``dense_params``, ``reference_head``; ``lib/builders`` has
the list), and a lookup comes out as the configuration's ``combiner``
says: ``'sum'`` over the hotness, or ``null``, every row kept (``[B,
hotness, width]``; ``[B, width]`` at hotness 1, as the program's layer
returns it).

``precision='control'`` is the same computation one step of precision
lower in every part the configuration states (bfloat16 tables and
optimizer state, three-bit mantissas into every product): the step a
later PR would be tempted by.  ``fault`` plants one of the faults a
training cell can have in the reference put in the program's place.
"""

import concurrent.futures

import numpy as np

from benchmarks.lib import names, weights

FAULTS = ('state_unchanged', 'half_batch', 'no_exchange')


def _round_mantissa(x, bits):
  """float32 ``x`` rounded (half away) to ``bits`` explicit mantissa
  bits, exponent range kept: the mantissa of a narrower float without
  its overflow."""
  import jax
  import jax.numpy as jnp
  drop = 23 - bits
  x = jnp.asarray(x, jnp.float32)
  u = jax.lax.stop_gradient(x).view(jnp.uint32)
  u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1)
                                                     & 0xFFFFFFFF)
  # straight through: the bit pattern has no derivative, the value has
  return x + jax.lax.stop_gradient(u.view(jnp.float32) - x)


def _bf16(a):
  """numpy float32 rounded to bfloat16 and back (round to nearest even)."""
  u = np.ascontiguousarray(a, np.float32).view(np.uint32).astype(np.uint64)
  u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
  return u.astype(np.uint32).view(np.float32)


def _matmul(precision, control):
  import jax
  import jax.numpy as jnp
  if precision == 'control':
    bits = int(control['head_matmul_mantissa_bits'])
    return lambda a, b: jnp.matmul(_round_mantissa(a, bits),
                                   _round_mantissa(b, bits),
                                   precision=jax.lax.Precision.HIGHEST)
  return lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _row_sums(index, grads, num_rows):
  """float64 sums of ``grads [n, w]`` into ``num_rows`` rows by ``index``:
  a 0/1 matrix with one entry per gradient row, times the gradients."""
  import scipy.sparse
  n = len(index)
  pick = scipy.sparse.csr_matrix(
      (np.ones(n), (index, np.arange(n))), shape=(num_rows, n))
  return np.asarray(pick @ grads.astype(np.float64))


def _each(fn, count):
  """``[fn(0), ..., fn(count - 1)]`` on a few threads: the per-table work
  is NumPy on arrays of its own table, which releases the interpreter."""
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    return list(pool.map(fn, range(count)))


class _Optimizer:
  """Row-wise SGD, Adagrad (Keras semantics: ``acc += g**2``, then
  ``p -= lr * g / sqrt(acc + eps)`` with the new accumulator) or Adam
  (``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g**2``, ``p -= lr *
  (m / (1 - b1**t)) / (sqrt(v / (1 - b2**t)) + eps)``, ``t`` counting the
  steps of THAT row: the program's ``SparseAdam`` advances moments and
  count for touched rows only, and for a dense leaf, every element of
  which every step touches, the same rule is ``optax.adam``) in float32,
  on any array; ``store`` rounds what is kept between steps.  The state
  of a leaf is a dict of arrays that share its leading axis."""

  def __init__(self, spec, store):
    self.kind = spec['kind']
    if self.kind not in ('sgd', 'adagrad', 'adam'):
      raise ValueError(f'unknown optimizer kind {self.kind!r}')
    self.lr = np.float32(spec['learning_rate'])
    self.acc0 = np.float32(spec.get('initial_accumulator_value', 0.0))
    self.eps = np.float32(spec.get('epsilon', 0.0))
    self.b1 = np.float32(spec.get('b1', 0.0))
    self.b2 = np.float32(spec.get('b2', 0.0))
    self.store = store

  def init(self, p):
    if self.kind == 'adagrad':
      return {'acc': np.full(p.shape, self.store(self.acc0), np.float32)}
    if self.kind == 'adam':
      return {'m': np.zeros(p.shape, np.float32),
              'v': np.zeros(p.shape, np.float32),
              't': np.zeros(p.shape[:1] + (1,) * (p.ndim - 1), np.float32)}
    return {}

  def step(self, p, state, g):
    g = np.asarray(g, np.float32)
    if self.kind == 'sgd':
      return self.store(p - self.lr * g), {}
    if self.kind == 'adagrad':
      acc = self.store(state['acc'] + g * g)
      return self.store(p - self.lr * g / np.sqrt(acc + self.eps)), {
          'acc': acc}
    one = np.float32(1)
    t = state['t'] + one
    m = self.b1 * state['m'] + (one - self.b1) * g
    v = self.b2 * state['v'] + (one - self.b2) * g * g
    mhat = m / (one - self.b1**t)
    vhat = v / (one - self.b2**t)
    p = p - self.lr * mhat / (np.sqrt(vhat) + self.eps)
    return self.store(p), {'m': self.store(m), 'v': self.store(v), 't': t}

  def gradient_from_state(self, p0, p1, state1):
    """The first gradient as the optimizer got it, worked out from the
    state after one step (the same read-back the harness makes on the
    program's state): from the parameters' change under SGD and Adagrad;
    from the first moment under Adam, whose change is all but the
    gradient's sign (``m1 = (1 - b1) g`` from a zero moment)."""
    if self.kind == 'adam':
      return state1['m'].astype(np.float32) / (np.float32(1) - self.b1)
    delta = p0.astype(np.float32) - p1.astype(np.float32)
    if self.kind == 'sgd':
      return delta / self.lr
    return delta * np.sqrt(state1['acc'] + self.eps) / self.lr

  def leaf_readings(self, p0, p1, state1):
    """``(change_norm, grad_norm, moved)`` of one leaf between ``p0`` and
    ``p1``: the norm of the change, of the gradient worked out from the
    state, and the count of elements that moved at all."""
    grad = self.gradient_from_state(p0, p1, state1).astype(np.float64)
    return (float(np.linalg.norm(p1.astype(np.float64) - p0)),
            float(np.linalg.norm(grad)), int(np.count_nonzero(p1 != p0)))


def leaf_names(tree):
  """The leaves of a pytree by their tree paths, ``mlp/0/kernel``."""
  import jax
  part = lambda k: str(getattr(k, 'key', getattr(k, 'idx', getattr(
      k, 'name', k))))
  return ['/'.join(part(k) for k in path) for path, _ in
          jax.tree_util.tree_flatten_with_path(tree)[0]]


def run_reference(config, mix, seed, *, precision='stated', fault=None,
                  chips=1):
  """Follow ``mix['checked_steps']`` steps from ``seed``; return the
  readings ``{'loss': [...], 'grad_norm': {leaf: x}, 'moved': {leaf: n},
  'change_norm': {leaf: x}}`` with leaves ``table_<i>`` and the dense
  leaves by their tree paths (``mlp/0/kernel``): the first gradient's
  norm and the count of elements the first step moved, the change's norm
  after the last."""
  import jax
  if fault is not None and fault not in FAULTS:
    raise ValueError(f'unknown fault {fault!r}')
  steps = int(mix['checked_steps'])
  control = config['control_precision']
  store = _bf16 if precision == 'control' else (lambda a: a)
  combiner = config['combiner']
  if combiner not in (None, 'sum'):
    raise ValueError(f'combiner {combiner!r}: the reference has sum and null')
  specs, table_map, hotness = names.resolve(config['tables'])(config)
  inputs = [(specs[t][0], h) for t, h in zip(table_map, hotness)]
  pool = names.resolve(mix['generator'])(mix, inputs, config, seed,
                                         batches=steps)
  opt = _Optimizer(config['optimizer'], store)

  # the rows any checked batch touches, per table, and their state; the
  # tables the head reads too are held whole
  whole = set(config.get('head_reads_tables', ()))
  words = weights.table_words(seed, len(specs))
  inputs_of = [[i for i, t in enumerate(table_map) if t == tid]
               for tid in range(len(specs))]

  def first_rows(tid):
    rows_total, width, half = specs[tid]
    if tid in whole:
      uniq = np.arange(rows_total)
    else:
      uniq = np.unique(np.concatenate([cats[i].reshape(-1)
                                       for cats, _ in pool
                                       for i in inputs_of[tid]]))
    return uniq, store(weights.numpy_rows(words[tid], uniq, width, half))

  touched, rows0 = zip(*_each(first_rows, len(specs)))
  rows = [w0.copy() for w0 in rows0]
  accs = [opt.init(w0) for w0 in rows0]
  dense0 = names.resolve(config['dense_params'])(config, seed)
  dense0 = jax.tree.map(store, dense0)
  dense_names = leaf_names(dense0)
  flat_dense, tree = jax.tree.flatten(jax.tree.map(np.copy, dense0))
  dense_state = [opt.init(p) for p in flat_dense]

  head = names.resolve(config['reference_head'])(config)
  matmul = _matmul(precision, control)
  grad_fn = jax.jit(jax.value_and_grad(
      lambda d, e, tabs, b: head(d, e, b, matmul, tabs), argnums=(0, 1, 2)))

  # half of the batch left out, the mean taken over the rest: the first
  # half of every array's leading axis (ids, lookups and batch leaves are
  # all laid out sample by sample)
  keep = ((lambda a: a[:a.shape[0] // 2]) if fault == 'half_batch'
          else (lambda a: a))

  def mine(tid, count):
    """Without the exchange a chip sees only its own batch shard's ids
    for its own tables: every other (sample, table) pair reads zeros."""
    return (np.arange(count) * chips // count) == (tid % chips)

  def dense_leaves(flat1, state1):
    return zip(dense_names, jax.tree.leaves(dense0), flat1, state1)

  losses, grad_norm, moved, change_norm = [], {}, {}, {}
  for k in range(steps):
    cats, batch = pool[k]
    index = [np.searchsorted(touched[t], cats[i]) for i, t in
             enumerate(table_map)]

    def looked_up(i):
      t = table_map[i]
      out = rows[t][index[i]]                          # [B, hotness, width]
      if out.shape[1] == 1:
        out = out[:, 0]
      elif combiner == 'sum':
        out = out.sum(axis=1, dtype=np.float32)
      if fault == 'no_exchange':
        out = out * mine(t, len(out)).reshape((-1,) + (1,) * (out.ndim - 1))
      return keep(out)

    emb = _each(looked_up, len(table_map))
    loss, (d_dense, d_emb, d_tables) = grad_fn(
        tree.unflatten(flat_dense), tuple(emb),
        {t: rows[t] for t in sorted(whole)}, jax.tree.map(keep, batch))
    losses.append(float(loss))
    if fault == 'state_unchanged':
      continue
    d_emb = [np.asarray(d) for d in d_emb]

    def applied(t):
      """Sparse apply of table ``t``: sum duplicates (and what the head
      itself sent back, where it reads the table), then one row-wise
      update per row the step touched."""
      width = specs[t][1]
      g = np.zeros((touched[t].size, width), np.float64)
      hit = np.full(touched[t].size, t in whole)
      for i in inputs_of[t]:
        cot = d_emb[i]
        if fault == 'no_exchange':
          cot = cot * keep(mine(t, len(index[i]))).reshape(
              (-1,) + (1,) * (cot.ndim - 1))
        idx = keep(index[i])
        hit[idx.reshape(-1)] = True
        if cot.ndim == 2:                    # one cotangent per sample
          cot = np.repeat(cot, idx.shape[1], axis=0)
        g += _row_sums(idx.reshape(-1), cot.reshape(-1, width),
                       touched[t].size)
      if t in whole:
        g += np.asarray(d_tables[t], np.float64)
      # a row the batch asked for is touched whatever its gradient: under
      # Adam a zero gradient still decays its moments and counts a step
      hit = np.flatnonzero(hit)
      new_rows, new_state = opt.step(
          rows[t][hit], {n: a[hit] for n, a in accs[t].items()}, g[hit])
      rows[t][hit] = new_rows
      for n, a in new_state.items():
        accs[t][n][hit] = a

    _each(applied, len(specs))
    stepped = [opt.step(p, s, np.asarray(g)) for p, s, g in
               zip(flat_dense, dense_state, tree.flatten_up_to(d_dense))]
    flat_dense = [s[0] for s in stepped]
    dense_state = [s[1] for s in stepped]
    if k == 0:
      first = [(f'table_{t}', rows0[t], rows[t], accs[t])
               for t in range(len(specs))]
      for name, p0, p1, s1 in first + list(dense_leaves(flat_dense,
                                                        dense_state)):
        _, grad_norm[name], moved[name] = opt.leaf_readings(p0, p1, s1)
  if fault == 'state_unchanged':
    leaves = [f'table_{t}' for t in range(len(specs))] + dense_names
    grad_norm, moved = dict.fromkeys(leaves, 0.0), dict.fromkeys(leaves, 0)
  last = [(f'table_{t}', rows0[t], rows[t]) for t in range(len(specs))]
  last += [leaf[:3] for leaf in dense_leaves(flat_dense, dense_state)]
  for name, p0, p1 in last:
    change_norm[name] = float(np.linalg.norm(p1.astype(np.float64) - p0))
  return {'loss': losses, 'grad_norm': grad_norm, 'moved': moved,
          'change_norm': change_norm}


# gradients the reference holds to be nought to rounding: a leaf whose
# first gradient is under this share of the median leaf's moves by
# round-off alone, and is left out of the change comparison
NOUGHT = 1e-3


def compare(program, reference):
  """The numbers that decide ``correct``, each the worst of its kind:

  - ``loss_gap``: ``|program - reference| / |reference|`` over the
    checked steps' losses;
  - ``grad_gap``: over the leaves, the gap between the program's and the
    reference's norm of the first gradient, against the reference's norm
    of that leaf or of the median leaf, whichever is larger;
  - ``change_gap``: the same for the norm of the parameters' change after
    the checked steps, over the leaves whose reference gradient is not
    nought (``NOUGHT``);
  - ``moved_gap``: over the leaves, the gap between the counts of elements
    that the first step moved at all, against the reference's count: which
    rows a step touches does not depend on how coherent the gradients are,
    so this is the number that a batch half left out cannot pass.
  Returns ``(numbers, worst)``, ``worst`` naming the leaf or step."""
  numbers, worst = {}, {}
  gaps = [abs(p - r) / abs(r) for p, r in zip(program['loss'],
                                              reference['loss'])]
  gaps = [g if np.isfinite(g) else float('inf') for g in gaps]
  numbers['loss_gap'] = max(gaps)
  worst['loss_gap'] = f'step {int(np.argmax(gaps)) + 1}'
  median_grad = float(np.median(list(reference['grad_norm'].values())))
  for key, name, leaves in (
      ('grad_norm', 'grad_gap', list(reference['grad_norm'])),
      ('change_norm', 'change_gap',
       [l for l, g in reference['grad_norm'].items()
        if g >= NOUGHT * median_grad])):
    median = float(np.median(list(reference[key].values())))
    gap = {}
    for leaf in leaves:
      g = (abs(program[key][leaf] - reference[key][leaf])
           / max(reference[key][leaf], median, 1e-300))
      gap[leaf] = g if np.isfinite(g) else float('inf')
    numbers[name] = max(gap.values())
    worst[name] = max(gap, key=gap.get)
  gap = {leaf: abs(program['moved'][leaf] - n) / max(n, 1)
         for leaf, n in reference['moved'].items()}
  numbers['moved_gap'] = max(gap.values())
  worst['moved_gap'] = max(gap, key=gap.get)
  return numbers, worst
