"""How evenly the resident slabs lie over the mesh's devices.

``/debug/vars.deviceBlockCache.perDeviceBytes`` is the resident bytes by
device id, summed over every cached array's addressable shards
(``pilosa_tpu/parallel/residency.py``). A slab sharded over the slice
axis gives each of ``mesh_devices`` devices an equal share: 100. A slab
that landed whole on one device, or a device that holds nothing (missing
from the map: it counts 0), reads 0. Read after the window."""


def read(run):
    n_dev = run.config.get("mesh_devices")
    if not n_dev or run.after is None:
        return None
    cache = run.after["vars"].get("deviceBlockCache") or {}
    per_device = cache.get("perDeviceBytes")
    if not per_device:
        return None
    held = sorted(per_device.values(), reverse=True)
    held += [0] * (n_dev - len(held))
    if held[0] <= 0:
        return None
    return 100.0 * min(held) / held[0]
