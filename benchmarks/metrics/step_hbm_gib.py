"""GiB of HBM the compiled step needs on one chip: arguments plus
temporaries by ``compiled.memory_analysis()`` (``peak_bytes_in_use``
misses executable temporaries on this runtime, PERF.md section 6)."""


def read(context):
  memory = context['memory_analysis']
  if memory is None:
    return None
  return (memory.argument_size_in_bytes + memory.temp_size_in_bytes) / 2**30
