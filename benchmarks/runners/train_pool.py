"""The runner of traffic kind ``train_pool``: whole training batches in a
device-resident pool, cycled in order through the compiled step
(``lib/train.py`` has the set-up, the checked steps and the window)."""
from benchmarks.lib.train import run  # noqa: F401
