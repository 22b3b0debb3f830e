"""Four host devices for every rehearsal these tests start: the four-chip
cell's mesh is data=2 x model=2, and the one-chip cells take the first device.
Must run before JAX is imported; children inherit it through the environment.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
