"""The plain reference for training cells, its control and its faults,
and the comparison that decides ``correct``.

The reference follows the first steps of a run from the seed alone: it
draws the same batches (``lib/traffic``), computes the table rows it
needs from the seed (``lib/weights``), and trains them with row-wise
float32 arithmetic written out here.  It imports nothing of the program
and reads nothing the program made.  Only rows that a checked batch
touches are ever held, so it fits whatever the tables' size.

``precision='control'`` is the same computation one step of precision
lower in every part the configuration states (bfloat16 tables and
optimizer state, three-bit mantissas into every product): the step a
later PR would be tempted by.  ``fault`` plants one of the faults a
training cell can have in the reference put in the program's place.
"""

import concurrent.futures

import numpy as np

from benchmarks.lib import builders, names, traffic, weights

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
  """Row-wise SGD or Adagrad (Keras semantics: ``acc += g**2``, then
  ``p -= lr * g / sqrt(acc + eps)`` with the new accumulator) in
  float32, on any array; ``store`` rounds what is kept between steps."""

  def __init__(self, spec, store):
    self.kind = spec['kind']
    self.lr = np.float32(spec['learning_rate'])
    self.acc0 = np.float32(spec.get('initial_accumulator_value', 0.0))
    self.eps = np.float32(spec.get('epsilon', 0.0))
    self.store = store

  def init(self, p):
    return (np.full(p.shape, self.store(self.acc0), np.float32)
            if self.kind == 'adagrad' else None)

  def step(self, p, acc, g):
    g = np.asarray(g, np.float32)
    if self.kind == 'sgd':
      return self.store(p - self.lr * g), None
    acc = self.store(acc + g * g)
    return self.store(p - self.lr * g / np.sqrt(acc + self.eps)), acc

  def gradient_from_state(self, p0, p1, acc1):
    """The first gradient as the optimizer got it, worked out from the
    state after one step (the same read-back the harness makes on the
    program's state)."""
    delta = p0.astype(np.float32) - p1.astype(np.float32)
    if self.kind == 'sgd':
      return delta / self.lr
    return delta * np.sqrt(acc1 + self.eps) / self.lr

  def leaf_readings(self, p0, p1, acc1):
    """``(change_norm, grad_norm, moved)`` of one leaf between ``p0`` and
    ``p1``: the norm of the change, of the gradient worked out from it,
    and the count of elements that moved at all."""
    grad = self.gradient_from_state(p0, p1, acc1).astype(np.float64)
    return (float(np.linalg.norm(p1.astype(np.float64) - p0)),
            float(np.linalg.norm(grad)), int(np.count_nonzero(p1 != p0)))


def run_reference(config, mix, seed, *, precision='stated', fault=None,
                  chips=1):
  """Follow ``mix['checked_steps']`` steps from ``seed``; return the
  readings ``{'loss': [...], 'grad_norm': {leaf: x}, 'moved': {leaf: n},
  'change_norm': {leaf: x}}`` with leaves ``table_<i>`` and
  ``<mlp>/<layer>/<kernel|bias>``: the first gradient's norm and the
  count of elements the first step moved, the change's norm after the
  last."""
  import jax
  import jax.numpy as jnp
  if fault is not None and fault not in FAULTS:
    raise ValueError(f'unknown fault {fault!r}')
  steps = int(mix['checked_steps'])
  control = config['control_precision']
  store = _bf16 if precision == 'control' else (lambda a: a)
  specs, table_map, hotness = builders.table_specs(config)
  inputs = [(specs[t][0], h) for t, h in zip(table_map, hotness)]
  pool = traffic.train_pool(mix, inputs, config['num_numerical_features'],
                            seed, batches=steps)
  batch = int(mix['global_batch'])
  opt = _Optimizer(config['optimizer'], store)

  # the rows any checked batch touches, per table, and their state
  words = weights.table_words(seed, len(specs))
  inputs_of = [[i for i, t in enumerate(table_map) if t == tid]
               for tid in range(len(specs))]

  def first_rows(tid):
    ids = np.concatenate([cats[i].reshape(-1) for cats, _, _ in pool
                          for i in inputs_of[tid]])
    uniq = np.unique(ids)
    _, width, half = specs[tid]
    return uniq, store(weights.numpy_rows(words[tid], uniq, width, half))

  touched, rows0 = zip(*_each(first_rows, len(specs)))
  rows = [w0.copy() for w0 in rows0]
  accs = [opt.init(w0) for w0 in rows0]
  dense0 = builders.dense_params(config, seed)
  dense0 = jax.tree.map(store, dense0)
  dense = jax.tree.map(np.copy, dense0)
  dense_acc = jax.tree.map(opt.init, dense)

  head = names.resolve(config['reference_head'])(config)
  matmul = _matmul(precision, control)
  grad_fn = jax.jit(jax.value_and_grad(
      lambda d, e, num, lab: head(d, e, num, lab, matmul), argnums=(0, 1)))

  keep = np.ones(batch, bool)
  if fault == 'half_batch':
    keep[batch // 2:] = False        # the mean is taken over the rest
  owner_chip = None
  if fault == 'no_exchange':
    # without the exchange a chip sees only its own batch shard's ids
    # for its own tables: every other (sample, table) pair reads zeros
    owner_chip = np.arange(len(specs)) % chips
    sample_chip = np.arange(batch) * chips // batch

  losses, grad_norm, moved, change_norm = [], {}, {}, {}
  for k in range(steps):
    cats, numerical, labels = pool[k]
    index = [np.searchsorted(touched[t], cats[i]) for i, t in
             enumerate(table_map)]

    def looked_up(i):
      t = table_map[i]
      out = rows[t][index[i]]
      out = out[:, 0] if out.shape[1] == 1 else out.sum(axis=1,
                                                        dtype=np.float32)
      if owner_chip is not None:
        out = out * (sample_chip == owner_chip[t])[:, None]
      return out[keep]

    emb = _each(looked_up, len(table_map))
    loss, (d_dense, d_emb) = grad_fn(dense, tuple(emb), numerical[keep],
                                     labels[keep])
    losses.append(float(loss))
    if fault == 'state_unchanged':
      continue
    d_dense = jax.tree.map(np.asarray, d_dense)
    before = ((jax.tree.map(np.copy, dense), [r.copy() for r in rows])
              if k == 0 else None)
    d_emb = [np.asarray(d) for d in d_emb]

    def applied(t):
      """Sparse apply of table ``t``: sum duplicates, then one row-wise
      update per row that got a gradient."""
      g = np.zeros((touched[t].size, specs[t][1]), np.float64)
      for i in inputs_of[t]:
        cot = d_emb[i]
        if owner_chip is not None:
          cot = cot * (sample_chip == owner_chip[t])[keep][:, None]
        idx = index[i][keep]
        g += _row_sums(idx.reshape(-1), np.repeat(cot, idx.shape[1], axis=0),
                       touched[t].size)
      hit = np.flatnonzero(np.any(g != 0, axis=1))
      new_rows, new_acc = opt.step(
          rows[t][hit], None if accs[t] is None else accs[t][hit], g[hit])
      rows[t][hit] = new_rows
      if accs[t] is not None:
        accs[t][hit] = new_acc

    _each(applied, len(specs))
    flat_p, tree = jax.tree.flatten(dense)
    flat_a = tree.flatten_up_to(dense_acc) if opt.kind == 'adagrad' else (
        [None] * len(flat_p))
    stepped = [opt.step(p, a, g) for p, a, g in
               zip(flat_p, flat_a, tree.flatten_up_to(d_dense))]
    dense = tree.unflatten([s[0] for s in stepped])
    if opt.kind == 'adagrad':
      dense_acc = tree.unflatten([s[1] for s in stepped])
    if k == 0:
      first = [(f'table_{t}', before[1][t], rows[t], accs[t])
               for t in range(len(specs))]
      first += _dense_leaves(before[0], dense, dense_acc, opt)
      for name, p0, p1, a1 in first:
        _, grad_norm[name], moved[name] = opt.leaf_readings(p0, p1, a1)
  if fault == 'state_unchanged':
    leaves = [f'table_{t}' for t in range(len(specs))] + [
        n for n, *_ in _dense_leaves(dense0, dense, dense_acc, opt)]
    grad_norm, moved = dict.fromkeys(leaves, 0.0), dict.fromkeys(leaves, 0)
  last = [(f'table_{t}', rows0[t], rows[t]) for t in range(len(specs))]
  last += [leaf[:3] for leaf in _dense_leaves(dense0, dense, dense_acc, opt)]
  for name, p0, p1 in last:
    change_norm[name] = float(np.linalg.norm(p1.astype(np.float64) - p0))
  return {'loss': losses, 'grad_norm': grad_norm, 'moved': moved,
          'change_norm': change_norm}


def _dense_leaves(dense0, dense1, dense_acc, opt):
  """``(name, p0, p1, acc1)`` per dense leaf, names ``mlp/0/kernel``."""
  for mlp in sorted(dense0):
    for i, layer in enumerate(dense0[mlp]):
      for leaf in ('kernel', 'bias'):
        acc = (dense_acc[mlp][i][leaf] if opt.kind == 'adagrad' else None)
        yield (f'{mlp}/{i}/{leaf}', layer[leaf], dense1[mlp][i][leaf], acc)


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
