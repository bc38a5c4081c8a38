"""Mean device time of one prefill chunk (the executable whose operations stand
under ``serve_step.prefill``) in the Mamba-2 mixer: its projections, the
conv (``ssm.conv``), the scan (``ssm.scan``) and a call's rows of the state
arrays (``ssm.state``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "prefill", "ssm")
