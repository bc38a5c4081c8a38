"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the shared experts' products
(``experts.shared``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "experts_shared")
