"""Where JAX's persistent compilation cache lives: decided OUTSIDE the
program.

The directory is part of every cache key, so a directory that moves
never hits; and the machine a program runs on (a CI box, the chip tool's
sealed copy) is the only party that knows which directory survives the
process.  One rule for every entry point that compiles (bench.py,
chip_smoke.py, tests/conftest.py, examples/dlrm, examples/benchmarks):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; set no
  directory.
- unset: ``<checkout>/.jax_cache`` (git-ignored), a fixed path with no
  pid, time or temp component in it.

Either way the cache's key takes the program's metadata in
(``jax_compilation_cache_include_metadata_in_key``): the device phases
(``obs.trace.phase``) are metadata of the executable, and a step whose
phases changed must not be loaded with the old ones, or a trace books
its time to scopes the source no longer has.  The price: the key then
holds source locations too, so an executable is found again only by the
same source at the same path, which is what a check-out run twice is.
"""

from __future__ import annotations

import os

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
  """Point JAX at the cache directory by the rule above; returns the
  directory in use.  Call before the first compile."""
  import jax
  jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
  from_env = os.environ.get(ENV_VAR)
  if from_env:
    return from_env
  path = os.path.join(_CHECKOUT, '.jax_cache')
  jax.config.update('jax_compilation_cache_dir', path)
  return path
