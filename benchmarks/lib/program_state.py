"""The program's embedding state: written from the seed and read back, on
the device.

The one file that knows how the program lays a table out: a table is a
contiguous run of the flat, row-major storage of its fusion group's
shard on one device (``plan.shard_layout()``, the contract the program's
own checkpoint code reads).  Everything else here is this benchmark's.

``make_tables`` writes every chip's shards in one jitted call, each
element the counter hash of its position in its table (``lib/weights``).
The program's own ``dist.init`` is not used: it holds a width-128 shard
twice while it builds it (9.5 GiB of temporaries beside 9.5 GiB of output
for dlrm-mlperf on four chips, v5e compile-only, PERF.md section 7), and
what it draws could not be recomputed row by row in the reference.

``table_readings`` recomputes the initial table inside the reduction, so
no second copy of a table is ever held, and a reading costs one pass over
the table at HBM speed.  For table ``t`` after a step: ``sum((W -
W0)**2)``, the sum of squares of the first gradient worked out from the
state (``(W0 - W) / lr`` for SGD, ``(W0 - W) * sqrt(acc + eps) / lr`` for
Adagrad, ``m / (1 - b1)`` for Adam, as the reference reads it back from its
own state), and how many elements moved at all.  ``dense_states`` hands
the same reading the dense leaves' part of the optax state.
"""

import functools

import numpy as np

from benchmarks.lib import weights

CHUNK_ROWS = 131072  # storage rows per pass: 64 MiB of float32 at 128 lanes
_NEVER = 0xFFFFFFFF  # a start no flat position reaches


def table_layout(dist):
  """Per table ``(leaf_key, device_index, flat_start, count)`` in the
  group shard's flat storage.  Raises for a sliced table: the hashed
  initial value and this reading cover whole tables only."""
  plan = dist.plan
  group_of = {g.key: gi for gi, g in enumerate(plan.groups)}
  out = []
  for tid, shards in enumerate(plan.shard_layout()):
    cfg = plan.table_configs[tid]
    if len(shards) != 1:
      raise NotImplementedError(f'table {tid} is sliced into {len(shards)}')
    dev, key, row_offset, c0, c1, r0, r1, stride = shards[0]
    if (c0, c1, r0, r1, stride) != (0, cfg.output_dim, 0, cfg.input_dim, 1):
      raise NotImplementedError(f'table {tid} is sliced: {shards[0]}')
    out.append((f'group_{group_of[key]}', dev, row_offset * cfg.output_dim,
                cfg.input_dim * cfg.output_dim))
  return out


def make_tables(dist, layout, specs, words):
  """The embedding parameters ``{group_i: [D, param_rows, param_width]}``
  as the program shards them, float32, written on the device.

  ``specs[t]`` is ``(rows, width, half_range)`` and ``words[t]`` the two
  key words of table ``t``.  Which table an element belongs to is run-time
  data (sorted starts per chip, picked by the chip's index), so one
  compiled program serves every seed; past the last table a shard is 0."""
  import jax
  import jax.numpy as jnp
  from jax.sharding import PartitionSpec as P
  world = dist.world_size
  shapes, meta = {}, {}
  for gi, g in enumerate(dist.plan.groups):
    key = f'group_{gi}'
    if g.param_rows * g.param_width >= 2**32:
      raise ValueError(f'{key}: shard too large for a 32-bit flat position')
    per_dev = [[] for _ in range(world)]
    for tid, (leaf, dev, start, count) in enumerate(layout):
      if leaf == key:
        per_dev[dev].append((start, words[tid][0], words[tid][1],
                             weights.value_scale(specs[tid][2]),
                             start + count))
    slots = max(len(d) for d in per_dev) + 1
    table = np.zeros((4, world, slots), np.uint32)
    table[0] = _NEVER
    for dev, entries in enumerate(per_dev):
      entries.sort()
      for k, (start, w0, w1, scale, _) in enumerate(entries):
        table[:, dev, k] = (start, w0, w1, np.float32(scale).view(np.uint32))
      # the padding past the last table: scale 0 writes zeros
      table[0, dev, len(entries)] = entries[-1][4] if entries else 0
    shapes[key] = (g.param_rows, g.param_width)
    meta[key] = table

  def build(meta):
    me = jax.lax.axis_index(dist.axis_name)
    out = {}
    for key, (rows, lanes) in shapes.items():
      start, w0, w1, scale = (meta[key][k][me] for k in range(4))
      flat = (jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 0)
              * jnp.uint32(lanes)
              + jax.lax.broadcasted_iota(jnp.uint32, (rows, lanes), 1))
      zero = jnp.zeros((), jnp.uint32)
      mine = [zero, zero, zero, zero]       # start, word 0, word 1, scale
      for k in range(start.shape[0]):       # starts ascend: the last hit wins
        inside = flat >= start[k]
        mine = [jnp.where(inside, v[k], m)
                for v, m in zip((start, w0, w1, scale), mine)]
      scale_f32 = jax.lax.bitcast_convert_type(mine[3], jnp.float32)
      out[key] = weights.hashed_values(jnp, flat - mine[0],
                                       (mine[1], mine[2]), scale_f32)[None]
    return out

  fn = jax.jit(jax.shard_map(
      build, mesh=dist.mesh, in_specs=P(),
      out_specs={key: P(dist.axis_name, None, None) for key in shapes},
      check_vma=False))
  return fn(meta)


# per optimizer kind: the leaf of the program's sparse optimizer state
# that the first gradient is worked out from (``None``: from the table
# alone), and where the optax state keeps the dense leaves' counterpart
_STATE_LEAF = {'sgd': None, 'adagrad': 'acc', 'adam': 'm'}
_DENSE_STATE = {'adagrad': lambda state: state[0].sum_of_squares,
                'adam': lambda state: state[0].mu}


def dense_states(kind, dense_opt_state):
  """The optax state of the dense leaves as the reference's optimizer
  names it: a pytree of the dense parameters' shape whose leaves are
  ``{name: array}`` (host numpy), or ``None`` where the kind keeps none."""
  import jax
  name = _STATE_LEAF[kind]
  if name is None:
    return None
  return jax.tree.map(lambda a: {name: np.asarray(a)},
                      _DENSE_STATE[kind](dense_opt_state))


@functools.lru_cache(maxsize=None)
def _reader(kind, lr, eps, b1):
  import jax
  import jax.numpy as jnp
  if kind not in _STATE_LEAF:
    raise ValueError(f'unknown optimizer kind {kind!r}')

  def read(leaf, acc, first_row, num_chunks, flat_start, count, words, scale):
    leaf = leaf.reshape(leaf.shape[-2:])
    rows_total, lanes = leaf.shape
    chunk = min(CHUNK_ROWS, rows_total)
    if acc is not None:
      acc = acc.reshape(acc.shape[-2:]).astype(jnp.float32)

    def body(i, carry):
      want = first_row + i * chunk
      start = jnp.minimum(want, rows_total - chunk)
      block = jax.lax.dynamic_slice(leaf, (start, 0), (chunk, lanes))
      row = start + jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 0)
      lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 1)
      flat = row.astype(jnp.uint32) * jnp.uint32(lanes) + lane.astype(
          jnp.uint32)
      mine = ((flat >= flat_start) & (flat < flat_start + count)
              & (row >= want))
      w0 = weights.hashed_values(jnp, flat - flat_start, words, scale)
      delta = jnp.where(mine, w0 - block.astype(jnp.float32), 0.0)
      if kind == 'adagrad':
        a = jax.lax.dynamic_slice(acc, (start, 0), (chunk, lanes))
        grad = delta * jnp.sqrt(a + jnp.float32(eps)) / jnp.float32(lr)
      elif kind == 'adam':
        a = jax.lax.dynamic_slice(acc, (start, 0), (chunk, lanes))
        grad = jnp.where(mine, a, 0.0) / (jnp.float32(1) - jnp.float32(b1))
      else:
        grad = delta / jnp.float32(lr)
      # compared, not subtracted: a backend that contracts the hash's
      # multiply into the subtraction leaves a rounding residue in delta
      moved = mine & (w0 != block)
      return (carry[0] + jnp.sum(delta * delta), carry[1] + jnp.sum(grad * grad),
              carry[2] + jnp.sum(moved, dtype=jnp.int32))

    zero = jnp.zeros((), jnp.float32)
    return jax.lax.fori_loop(0, num_chunks, body,
                             (zero, zero, jnp.zeros((), jnp.int32)))

  return jax.jit(read)


def _device_shard(array, dev):
  for shard in array.addressable_shards:
    if (shard.index[0].start or 0) == dev:
      return shard.data
  raise ValueError(f'no addressable shard for device index {dev}')


def table_readings(optimizer, specs, layout, emb_params, emb_opt_state,
                   words):
  """``[(change_norm, grad_norm, moved)]`` per table from the state as it
  stands (the caller says after which step that is).  ``optimizer`` is the
  configuration's own statement; one compiled reader per leaf shape."""
  read = _reader(optimizer['kind'], float(optimizer['learning_rate']),
                 float(optimizer.get('epsilon', 0.0)),
                 float(optimizer.get('b1', 0.0)))
  state_leaf = _STATE_LEAF[optimizer['kind']]
  pending = []
  for tid, (key, dev, flat_start, count) in enumerate(layout):
    leaf = _device_shard(emb_params[key], dev)
    acc = (_device_shard(emb_opt_state[key][state_leaf], dev)
           if state_leaf else None)
    rows_total, lanes = leaf.shape[-2:]
    first = flat_start // lanes
    cover = (flat_start + count - 1) // lanes - first + 1
    chunk = min(CHUNK_ROWS, rows_total)
    pending.append(read(
        leaf, acc, np.int32(first), np.int32(-(-cover // chunk)),
        np.uint32(flat_start), np.uint32(count),
        (words[tid][0], words[tid][1]),
        weights.value_scale(specs[tid][2])))
  return [(float(np.sqrt(float(c))), float(np.sqrt(float(g))), int(m))
          for c, g, m in pending]
