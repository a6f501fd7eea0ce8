import os

import hypothesis

# Tests run on the single real CPU device.  The multi-device dry-run tests
# spawn subprocesses with XLA_FLAGS set there (device count locks at first
# jax init, so it must NOT be set globally here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Under CI the property tests must be fully deterministic: a flaky random
# example would make the tier-1 job untrustworthy.
if os.environ.get("CI", "").lower() in ("1", "true"):
    hypothesis.settings.register_profile(
        "repro-ci", derandomize=True, deadline=None, database=None,
    )
    hypothesis.settings.load_profile("repro-ci")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
