"""Finding things by the names the manifest and the data files give."""

import importlib
import json
import os


def resolve(spec):
  """``module:function`` -> the function."""
  module, _, name = spec.partition(':')
  return getattr(importlib.import_module(module), name)


def load_json(root, kind, name):
  """``<root>/<kind>/<name>.json``, keys that start with ``_`` left out."""
  with open(os.path.join(root, kind, f'{name}.json')) as f:
    return {k: v for k, v in json.load(f).items() if not k.startswith('_')}


def set_runtime_env(mix, environ):
  """Put the mix's ``runtime_env`` into ``environ`` where the caller has
  not set the variable; only before JAX is imported does it reach the
  TPU runtime."""
  for key, value in mix.get('runtime_env', {}).items():
    environ.setdefault(key, str(value))
