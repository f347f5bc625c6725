"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's in-process virtual-cluster testing strategy
(reference: thrill/api/context.cpp:336-341 RunLocalTests over mock
clusters): all distributed tests run on XLA host-platform devices, no
real TPU needed.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

from thrill_tpu.common.platform import force_cpu_platform

force_cpu_platform()
