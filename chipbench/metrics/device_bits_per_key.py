"""Device bits per live key: what the store holds on the device at the
window's end, by the device allocator's own account (``bytes_in_use``
less what was in use before the store was built), times 8, over the live
keys. It counts the filter bank and whatever else the store keeps there."""


def read(run):
    if not run.store_device_bytes or run.store_device_bytes <= 0:
        return None
    return run.store_device_bytes * 8.0 / run.live_keys
