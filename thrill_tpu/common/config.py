"""Runtime configuration.

The reference framework is configured exclusively through environment
variables parsed at startup (reference: thrill/api/context.cpp:204-272,
1023-1093 — THRILL_NET, THRILL_RAM, THRILL_BLOCK_SIZE, THRILL_LOG, ...).
We keep the same model under the ``THRILL_TPU_`` namespace, plus
TPU-specific knobs (exchange mode, device platform).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def _env_str(name: str, default: Optional[str]) -> Optional[str]:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


def _env_flag(name: str, default: bool = True) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v not in ("0", "off", "false")


def overlap_enabled() -> bool:
    """THRILL_TPU_OVERLAP=0 restores the bulk-synchronous data plane
    exactly: single-dispatch phase-B exchanges, a host sync on every
    send-count matrix, and the serial per-peer host-frame sender.
    Master switch over the per-feature knobs (XCHG_CHUNKS,
    XCHG_CAP_CACHE, ASYNC_SEND)."""
    return _env_flag("THRILL_TPU_OVERLAP", True)


def cap_cache_enabled() -> bool:
    """THRILL_TPU_XCHG_CAP_CACHE=0 disables optimistic capacity-plan
    reuse: every exchange then syncs its [W, W] send-count matrix to
    the host before phase B, as before this knob existed."""
    return overlap_enabled() and _env_flag("THRILL_TPU_XCHG_CAP_CACHE",
                                           True)


def wire_compress_enabled() -> bool:
    """THRILL_TPU_WIRE_COMPRESS=0 restores the uncompressed wire on
    BOTH planes bit-identically: host frames ship the raw column codec
    (net/wire.py emits no compressed tags) and device exchanges ship
    rows at their declared dtypes (no phase-B narrowing). Master
    switch of the shrink-the-wire layer."""
    return _env_flag("THRILL_TPU_WIRE_COMPRESS", True)


def xchg_narrow_enabled() -> bool:
    """THRILL_TPU_XCHG_NARROW=0 disables just the device plane's
    phase-B row narrowing (data/exchange.py) while the host-frame
    codec stays on; results are bit-identical either way — narrowing
    is an exact integer cast chosen from observed ranges."""
    return wire_compress_enabled() and _env_flag(
        "THRILL_TPU_XCHG_NARROW", True)


def parse_si_iec_units(s: str) -> int:
    """Parse '100', '64K', '1Gi', '2GB' style size strings to bytes.

    Mirrors the semantics of tlx's parse_si_iec_units used by THRILL_RAM
    (reference: thrill/api/context.cpp:1027).
    """
    s = s.strip()
    mult = 1
    low = s.lower()
    for suffix, m in (
        ("kib", 1024), ("mib", 1024 ** 2), ("gib", 1024 ** 3), ("tib", 1024 ** 4),
        ("kb", 1000), ("mb", 1000 ** 2), ("gb", 1000 ** 3), ("tb", 1000 ** 4),
        ("ki", 1024), ("mi", 1024 ** 2), ("gi", 1024 ** 3), ("ti", 1024 ** 4),
        ("k", 1024), ("m", 1024 ** 2), ("g", 1024 ** 3), ("t", 1024 ** 4),
        ("b", 1),
    ):
        if low.endswith(suffix):
            mult = m
            s = s[: -len(suffix)]
            break
    return int(float(s.strip()) * mult)


def parse_kv_spec(spec: str, parse_value, what: str) -> dict:
    """Parse a "name=value,name=value" env spec, skipping malformed
    entries LOUDLY (a typo must not silently drop a tenant's weight or
    budget). ``parse_value`` converts and validates one value (raise
    ValueError to reject); shared by the service plane's weight and
    budget knobs (service/scheduler.py, service/tenancy.py)."""
    out: dict = {}
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, _, v = entry.partition("=")
        try:
            out[name.strip()] = parse_value(v)
        except (ValueError, IndexError):
            import sys
            print(f"thrill_tpu: malformed {what} entry {entry!r} "
                  f"ignored", file=sys.stderr)
    return out


# Where the persistent XLA compile cache goes when
# JAX_COMPILATION_CACHE_DIR does not place it (api/context.py): one
# fixed, git-ignored directory at the root of the checkout.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


@dataclasses.dataclass
class Config:
    """Host-level runtime configuration (one per HostContext)."""

    # Number of logical workers. 0 = one per local accelerator device.
    num_workers: int = 0
    # Preferred storage for ambiguous sources: 'device' or 'host'.
    default_storage: str = "device"
    # Exchange implementation: 'dense' (padded all_to_all; works on all
    # platforms; auto-switches to 1-factor rounds when the send matrix
    # is skewed), 'onefactor' (always W-1 ppermute rounds, each padded
    # to its own pair maximum — skew-proof), or 'ragged'
    # (lax.ragged_all_to_all; TPU-only fast path).
    exchange: str = "dense"
    # Item-capacity granularity for device block padding (power of two).
    block_items: int = 1024
    # Bytes of device memory the block pool may use (0 = autodetect).
    ram: int = 0
    # HBM budget for cached DIA node results (0 = unlimited). When the
    # budget is exceeded, cold EXECUTED node shards spill to the host
    # block store and are re-uploaded on their next pull.
    hbm_limit: int = 0
    # Host-DRAM budget for the spill block store (0 = autodetect: one
    # third of physical RAM, the reference's MemoryConfig split); past
    # this soft limit the store evicts blocks to disk.
    host_ram: int = 0
    # JSON event-log path pattern (None = disabled).
    log_path: Optional[str] = None
    # Directory for host-side spill files.
    spill_dir: str = "/tmp"
    # Enable periodic profiling.
    profile: bool = False
    # Durable checkpoint directory (api/checkpoint.py). Empty = the
    # whole checkpoint/resume subsystem is OFF (zero overhead, zero
    # behavior change — asserted by tests/api/test_checkpoint.py).
    ckpt_dir: str = ""
    # Resume from the newest complete checkpoint epoch on startup
    # (THRILL_TPU_RESUME=1; Run()/RunDistributed(resume=True) override).
    resume: bool = False
    # Auto-checkpoint every materialized DOp stage barrier, not just
    # explicit dia.Checkpoint() calls (THRILL_TPU_CKPT_AUTO=1).
    ckpt_auto: bool = False
    # Persistent plan store directory (service/plan_store.py): learned
    # exchange capacities, narrow specs, plan kinds and pre-shuffle
    # verdicts survive process restarts — a warm restart re-runs a
    # known pipeline with zero data-driven plan builds. Any vfs scheme
    # (file://, s3://, hdfs://). Empty = off (zero overhead).
    plan_store: str = ""

    @staticmethod
    def from_env() -> "Config":
        ram = os.environ.get("THRILL_TPU_RAM")
        hbm = os.environ.get("THRILL_TPU_HBM_LIMIT")
        return Config(
            num_workers=_env_int("THRILL_TPU_WORKERS", 0),
            default_storage=_env_str("THRILL_TPU_STORAGE", "device"),
            exchange=_env_str("THRILL_TPU_EXCHANGE", "dense"),
            block_items=_env_int("THRILL_TPU_BLOCK_ITEMS", 1024),
            ram=parse_si_iec_units(ram) if ram else 0,
            hbm_limit=parse_si_iec_units(hbm) if hbm else 0,
            host_ram=parse_si_iec_units(
                os.environ.get("THRILL_TPU_HOST_RAM") or "0"),
            log_path=_env_str("THRILL_TPU_LOG", None),
            spill_dir=_env_str("THRILL_TPU_SPILL_DIR", "/tmp"),
            profile=bool(_env_int("THRILL_TPU_PROFILE", 0)),
            ckpt_dir=_env_str("THRILL_TPU_CKPT_DIR", "") or "",
            resume=bool(_env_int("THRILL_TPU_RESUME", 0)),
            ckpt_auto=bool(_env_int("THRILL_TPU_CKPT_AUTO", 0)),
            plan_store=_env_str("THRILL_TPU_PLAN_STORE", "") or "",
        )


def round_up_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def round_up(n: int, granularity: int) -> int:
    return ((n + granularity - 1) // granularity) * granularity
