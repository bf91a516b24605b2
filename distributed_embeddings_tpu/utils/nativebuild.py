"""Shared build/staleness/load plumbing for the native C++ pieces.

Both ctypes-backed libraries (``utils/fastloader.py`` ->
``cc/libdetfastloader.so``, ``parallel/csr_native.py`` ->
``cc/libdetcsr.so``) follow the same lifecycle: build on demand with the
one ``cc/`` Makefile, refuse to let a stale binary shadow edited source
(ADVICE.md round 1), and degrade to their pure-Python twin — with a
logged warning, never in silence — when the toolchain or platform
cannot produce a loadable library.  This module is that lifecycle,
once, so the two bindings cannot drift — and so tier-1 tests share one
visible skip reason when no C++ toolchain is present.

Staleness is decided by CONTENT: each build records the digest of the
sources it compiled beside the binary (``<so>.srcsum``).  Modification
times are not consulted — a copied tree (the chip tool's, an unpacked
archive) does not keep them meaningful, and a binary that merely looks
newer than edited source must not be loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional, Sequence

_LOG = logging.getLogger(__name__)

CC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'cc')


def so_path(so_name: str) -> str:
  return os.path.join(CC_DIR, so_name)


def src_path(src_name: str) -> str:
  return os.path.join(CC_DIR, src_name)


def _src_digest(src_names: Sequence[str]) -> str:
  digest = hashlib.sha256()
  for name in ('Makefile',) + tuple(src_names):
    with open(src_path(name), 'rb') as f:
      digest.update(f.read())
  return digest.hexdigest()


def build(so_name: str, src_names: Sequence[str],
          quiet: bool = True) -> bool:
  """Compiles ``cc/<so_name>`` with make and records its sources'
  digest; returns success.

  False covers both a failed compile and a missing toolchain — callers
  fall back to the Python twin either way, and ``toolchain_note`` gives
  tests a visible skip reason.  ``make -B``: whether to rebuild was
  decided by ``stale`` from content, and make's own modification-time
  test must not overrule it.
  """
  cmd = ['make', '-B', '-C', CC_DIR, so_name]
  try:
    subprocess.run(cmd, check=True, capture_output=quiet)
  except (subprocess.CalledProcessError, FileNotFoundError):
    return False
  if not os.path.exists(so_path(so_name)):
    return False
  with open(so_path(so_name) + '.srcsum', 'w', encoding='ascii') as f:
    f.write(_src_digest(src_names))
  return True


def stale(so_name: str, src_names: Sequence[str]) -> bool:
  """True unless the library was built from exactly the present
  sources (a stale binary must not silently shadow edited source)."""
  try:
    with open(so_path(so_name) + '.srcsum', encoding='ascii') as f:
      return f.read().strip() != _src_digest(src_names)
  except OSError:
    return True


def load(so_name: str, src_names: Sequence[str]) -> Optional[ctypes.CDLL]:
  """Loads ``cc/<so_name>``, building (or rebuilding when stale) first.

  Returns None when the library cannot be built or loaded on this
  platform — unavailable, not fatal; callers fall back to Python, and
  the warning logged here says so.
  """
  if not os.path.exists(so_path(so_name)) or stale(so_name, src_names):
    if not build(so_name, src_names):
      _LOG.warning('%s not built (%s); its Python twin serves', so_name,
                   toolchain_note())
      return None
  try:
    return ctypes.CDLL(so_path(so_name))
  except OSError as e:
    # wrong arch/libc for this platform: unavailable, not fatal
    _LOG.warning('%s built but not loadable (%s); its Python twin '
                 'serves', so_name, e)
    return None


def toolchain_note() -> str:
  """One-line skip reason for tests gated on the native build."""
  cxx = os.environ.get('CXX', 'g++')
  try:
    subprocess.run([cxx, '--version'], capture_output=True, check=True)
    return f'native build failed despite {cxx} being present (see make -C cc)'
  except (subprocess.CalledProcessError, FileNotFoundError):
    return f'no C++ toolchain ({cxx} not found)'
