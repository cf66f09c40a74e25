"""Which accelerator this machine has, and who may use it.

One rule for the chip: a TPU belongs to ONE process at a time (libtpu
holds a machine-wide lock from backend init until the process exits).
On a node that is the TPU-flavour worker the raylet starts for a
TPU-resource lease; the driver, the GCS, the raylet and every
CPU-flavour worker are started with ``JAX_PLATFORMS=cpu`` set and never
initialise the TPU backend. Whether the node HAS chips is decided once,
where the ``TPU`` resource is counted (``api.init``: ``num_tpus=``,
``RAY_TPU_NUM_CHIPS``, else ``count_tpu_chips``); nothing downstream
probes again.

``count_tpu_chips`` is what the control plane may ask (no JAX);
``is_tpu`` is what kernel code asks inside a process that computes.
"""

from __future__ import annotations

import errno
import glob
import logging
import os
import time

logger = logging.getLogger("ray_tpu.accelerator")

_GOOGLE_PCI_VENDOR = "0x1ae0"
# TPU accelerator functions by PCI device id (v2/v3 .. v5e, v6e, 7x): the
# same ids JAX reads to decide whether the machine has a TPU at all
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"})


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def tpu_device_nodes() -> list[str]:
    """The device nodes of the TPU chips this machine lets a process
    open, found WITHOUT loading libtpu or initialising JAX (either would
    claim the chip for the caller). A chip is a PCI function with
    Google's vendor id and a TPU device id whose device node exists: its
    VFIO group under /dev/vfio (v5e and later), or an /dev/accel* node
    (earlier generations). The PCI bus alone over-counts — a one-chip
    machine cut from a four-chip host lists four functions and one node
    — and an installed ``libtpu`` package is no evidence of a chip at
    all."""
    functions = [
        os.path.dirname(vendor)
        for vendor in glob.glob("/sys/bus/pci/devices/*/vendor")
        if _read(vendor) == _GOOGLE_PCI_VENDOR
        and _read(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICES]
    groups = {os.path.basename(os.path.realpath(
        os.path.join(f, "iommu_group"))) for f in functions
        if os.path.exists(os.path.join(f, "iommu_group"))}
    if groups:
        nodes = [f"/dev/vfio/{g}" for g in sorted(groups)
                 if os.path.exists(f"/dev/vfio/{g}")]
    else:  # a sysfs without group links: every numbered VFIO node
        nodes = sorted(glob.glob("/dev/vfio/[0-9]*"))
    nodes = nodes or sorted(glob.glob("/dev/accel[0-9]*"))
    return nodes[:len(functions)]


def count_tpu_chips() -> int:
    """What the ``TPU`` resource of a node is counted from."""
    return len(tpu_device_nodes())


# How long a chip-owning worker waits for device nodes that another
# process still holds. A VFIO group opens once at a time, and a worker
# that ended keeps its nodes until the kernel has unpinned its memory:
# 17-24 s after a four-chip run (PERF.md section 6, PR 46).
CHIP_WAIT_BOUND_S = 60.0


def held_nodes(nodes: list[str]) -> list[str]:
    """Those of `nodes` that cannot be opened because another process
    holds them (EBUSY, and that error alone). Only the process that is
    about to own the chips asks: an open node is busy to everyone else,
    for the moment it stays open here."""
    held = []
    for node in nodes:
        try:
            os.close(os.open(node, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                held.append(node)
    return held


def wait_for_chips(probe, bound: float = CHIP_WAIT_BOUND_S,
                   pause: float = 0.25, facts: dict | None = None) -> float:
    """A chip-owning worker's wait for chips that are still being
    released, before it initialises the backend: polls `probe()` (the
    nodes still held) until it is empty or `bound` seconds have passed,
    and returns the seconds that took, the first probe included (under
    gVisor the open of a held node itself blocks for up to 3 s, and may
    come back free) — next to nothing, with nothing logged, when the
    chips are free at once. Past the bound the worker starts all the
    same and fails with libtpu's own error, as it would have without the
    wait. `facts`, if given, takes `held` (how many nodes the first probe
    found held) and `waited_s`: the `worker.chip_wait` span's attributes."""
    t0 = time.monotonic()
    held = probe()
    first = len(held)
    while held and time.monotonic() - t0 < bound:
        time.sleep(pause)
        held = probe()
    waited = time.monotonic() - t0
    if held:
        logger.warning("waited %.1f s for the chip and %s still held by "
                       "another process: starting all the same", waited,
                       ", ".join(held))
    elif waited >= 1.0:  # a probe of free nodes takes milliseconds
        logger.info("waited %.1f s for the chip: its device nodes were "
                    "still held by a process that was ending", waited)
    if facts is not None:
        facts.update(held=first, waited_s=round(waited, 4))
    return waited


def tpu_worker_jax_platforms() -> str:
    """The JAX_PLATFORMS value a TPU-flavour worker of this node is
    started with: ``tpu``, unless the process that starts the node has
    pinned JAX to something itself (the test tree and the CPU
    rehearsals set ``cpu`` and declare their chips), which the worker
    inherits. Nothing is probed: a declared or detected chip that
    cannot be opened fails in the worker with libtpu's error instead of
    silently computing on the CPU. Every other process of the node gets
    ``cpu``, set, whatever the ambient value."""
    return os.environ.get("JAX_PLATFORMS") or "tpu"


def is_tpu() -> bool:
    """True in a process whose default JAX backend is a TPU: Pallas
    kernels compile through Mosaic there and run interpreted everywhere
    else. Initialises the backend, so only compute processes call it."""
    import jax

    return jax.default_backend() == "tpu"
