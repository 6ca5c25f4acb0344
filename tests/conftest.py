import os

# Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
# exercised without TPU hardware (see task brief / SURVEY.md).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture()
def manager():
    from siddhi_tpu import SiddhiManager
    m = SiddhiManager()
    yield m
    m.shutdown()
