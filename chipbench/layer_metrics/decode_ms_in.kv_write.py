"""Mean device time of one decode step (the executable whose operations stand
under ``serve_step.decode``) in the new K/V rows written into their pages or
ring (``kv.write``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "decode", "kv_write")
