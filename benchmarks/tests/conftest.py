"""Four virtual CPU devices for the process that runs these tests.

The older modules' fixtures (``rehearsals``, ``traced``) rehearse every
cell of BENCHMARK.json INSIDE the pytest process, and the four-segment
cell asks for a four-device mesh: with one device its first send raises,
the fixture with it, and the two older cells' cases are lost too. The
flag has to be set before JAX starts, so it is set here, for the process.
A session at one segment runs on the first device as it always did.
``test_motion_cell.py`` makes its own rehearsals in a process each."""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
