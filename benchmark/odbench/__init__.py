"""The yardstick of the OpenDiLoCo-TPU benchmark.

Everything here belongs to the benchmark and imports nothing from
``opendiloco_tpu`` except inside the drivers (``train_cell``, ``serve_cell``),
which build the system under test. Later PRs add files beside these and edit
none of them.
"""
