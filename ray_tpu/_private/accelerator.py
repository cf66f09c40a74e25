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

import glob
import os

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


def count_tpu_chips() -> int:
    """TPU chips this machine lets a process open, counted WITHOUT
    loading libtpu or initialising JAX (either would claim the chip for
    the caller). A chip is a PCI function with Google's vendor id and a
    TPU device id whose device node exists: its VFIO group under
    /dev/vfio (v5e and later), or an /dev/accel* node (earlier
    generations). The PCI bus alone over-counts — a one-chip machine
    cut from a four-chip host lists four functions and one node — and
    an installed ``libtpu`` package is no evidence of a chip at all."""
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
        nodes = sum(os.path.exists(f"/dev/vfio/{g}") for g in groups)
    else:  # a sysfs without group links: every numbered VFIO node
        nodes = len(glob.glob("/dev/vfio/[0-9]*"))
    return min(len(functions),
               nodes or len(glob.glob("/dev/accel[0-9]*")))


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
