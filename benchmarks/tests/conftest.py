"""The benchmark's own tests run on the CPU with four virtual devices:
``python3 -m pytest benchmarks/tests -q`` from the root of the repo."""
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
if 'xla_force_host_platform_device_count' not in os.environ.get('XLA_FLAGS',
                                                                ''):
  os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
                             + ' --xla_force_host_platform_device_count=4')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
