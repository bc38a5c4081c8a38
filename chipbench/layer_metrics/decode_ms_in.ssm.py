"""Mean device time of one decode step (the executable whose operations stand
under ``serve_step.decode``) in the Mamba-2 mixer: its projections, the conv
(``ssm.conv``), the scan (``ssm.scan``) and a call's rows of the state
arrays (``ssm.state``); self times of the device events by their scope path,
``device_scopes.py``."""
import device_scopes


def read(trace, counters, record):
    return device_scopes.metric(trace, "decode", "ssm")
