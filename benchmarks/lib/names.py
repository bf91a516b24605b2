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
