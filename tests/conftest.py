import os

# Tests run on the single real CPU device. The 512-device dry-run sets
# XLA_FLAGS itself inside repro/launch/dryrun.py (and must NOT leak here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
