"""Backend compiles JAX reported inside the measured window (count).
Source: JAX's monitoring events, counted by the harness.  Should be 0."""


def read(context):
  return context['compiles_in_window']
