"""Device time under one op scope of the program, a call of each kind of
step executable: a helper of the ``*_ms_in.attn_absorb`` readers under
``layer_metrics/``; no metric of its own.

``device_scopes.py``'s tables give every device operation to one part,
and an op scope they do not hold is skipped: latent attention's absorbed
products (``attn.absorb``, ``mxnet_tpu/profiler/core.py``) stand inside
the block ``attention`` and so count in ``attn_proj`` there. This module
reads the same file with the same functions (``read_events``,
``tokens_of``, ``trace_reduce.self_times``) and sums the self time of the
operations whose scope path holds the asked name, over the whole calls
inside the window, by the kind their step scope marks. The number is a
part *of* ``<kind>_ms_in.attn_proj``, not beside it. A program without
the scope, a trace without a window or another run's file read None.
"""
import bisect

import device_scopes
import trace_reduce


def scope_ms(chips, window, scope):
    """``{kind: mean ms a call}`` under ``scope``; a kind appears once
    any operation of its calls stood under the scope."""
    t0, t1 = window
    ns, calls = {}, {}
    for chip in chips:
        whole = sorted((s, e) for _, s, e in chip["modules"]
                       if s >= t0 and e <= t1)
        starts = [c[0] for c in whole]
        inside = [[] for _ in whole]
        for i, (_, s, e, _) in enumerate(chip["ops"]):
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and e <= whole[j][1]:
                inside[j].append((i, s, e))
        for evs in inside:
            by_kind, under = {}, 0
            for i, self_ns in trace_reduce.self_times(evs):
                step, toks = device_scopes.tokens_of(chip["ops"][i][3])
                if step is None:
                    continue
                kind = device_scopes.KINDS[step]
                by_kind[kind] = by_kind.get(kind, 0) + self_ns
                if scope in toks[:-1]:
                    under += self_ns
            if by_kind:
                kind = max(by_kind, key=by_kind.get)
                calls[kind] = calls.get(kind, 0) + 1
                ns[kind] = ns.get(kind, 0) + under
    return {k: ns[k] / calls[k] / 1e6 for k in ns if ns[k]}


def metric(trace, kind, scope):
    """Mean device ms a call of ``kind`` under ``scope`` in the run
    ``trace`` came from, read once a scope and kept on it."""
    kept = trace.__dict__.setdefault("latent_scopes", {})
    if scope not in kept:
        kept[scope] = {}
        path = device_scopes.newest_trace()
        if path is not None:
            window, chips = device_scopes.read_events(path)
            if window is not None and window == (trace.t0, trace.t1):
                kept[scope] = scope_ms(chips, window, scope)
    return kept[scope].get(kind)
