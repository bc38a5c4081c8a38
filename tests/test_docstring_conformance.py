"""Reference docstring conformance: the reference's OWN ``>>>`` examples,
executed verbatim against this build's public surfaces.

Round-4 verdict, Next #3 generalized: the registry audit pins op *names*
and ``test_sparse_ctor_conformance`` pins the sparse ctor docstrings; this
suite sweeps whole reference source files through
:mod:`docstring_harness`, so *signatures and semantics* documented in the
reference are executed, not just resolvable.  Each parametrized case is
one reference file (examples inside a docstring share state); without the
reference tree (``docstring_harness.REF_ROOT``) the cases skip.

``SKIPS`` is the documented divergence surface: every entry is either a
reference-side doctest defect (typos, missing ``...`` continuations, py2
reprs the comparator cannot normalize) or a justified redesign with its
rationale stated inline.  An entry may be a ``qualname`` (whole block) or
``(qualname, example_idx)``.

Legacy files run under ``mx.util.set_np(array=False)``, the reference's
default mode for the ``mx.nd`` era (this build defaults to numpy mode).
"""
import pytest

import mxnet_tpu as mx
from docstring_harness import (ExampleFailure, collect_blocks,
                               default_globs, reset_mode, run_block)


def _ndarray_extra_globs():
    from mxnet_tpu.ndarray.ndarray import indexing_key_expand_implicit_axes
    return {"indexing_key_expand_implicit_axes":
            indexing_key_expand_implicit_axes}


def _linalg_extra_globs():
    return {"LA": mx.np.linalg}


def _batchify_extra_globs():
    from mxnet_tpu.gluon.data import batchify
    return {"batchify": batchify, "Stack": batchify.Stack,
            "Pad": batchify.Pad, "Append": batchify.Append,
            "Group": batchify.Group, "AsList": batchify.AsList}


FILES = {
    "context.py": dict(legacy=True, skips={}, extra=None),
    "ndarray/ndarray.py": dict(
        legacy=True,
        extra=_ndarray_extra_globs,
        skips={
            "NDArray._sync_copyfrom":
                "reference docstring typo: the output line is prefixed "
                "'>> ' so doctest attaches the want to the assignment",
            "NDArray.dtype":
                "legacy .dtype returns the np.dtype instance, not the "
                "numpy scalar class; == comparisons with either spelling "
                "behave identically",
            "NDArray.astype": "same np.dtype-instance repr as NDArray.dtype",
            "NDArray.to_dlpack_for_read":
                "returns a live __dlpack__ exporter (keeps the buffer "
                "alive across consumers) instead of a consumed-once "
                "PyCapsule — documented redesign, mxnet_tpu/dlpack.py",
            "NDArray.to_dlpack_for_write": "same exporter redesign",
            ("indexing_key_expand_implicit_axes", 5):
                "malformed doctest in the reference: array literal "
                "continued without '...' markers",
            ("indexing_key_expand_implicit_axes", 6):
                "depends on the malformed example above",
        }),
    "ndarray/sparse.py": dict(
        legacy=True, extra=None,
        skips={
            "BaseSparseNDArray.astype":
                "np.dtype-instance repr, same as NDArray.dtype",
            ("CSRNDArray.__setitem__", 4):
                "reference docstring bug: assigns the zeros array into x "
                "yet documents x as all-ones; the reference's own "
                "implementation (sparse.py:437 value.copyto(self)) "
                "produces zeros",
            ("CSRNDArray.asscipy", 3):
                "scipy repr format drift: modern scipy prints 'with 0 "
                "stored elements and shape (2, 3)', the want predates it",
            "RowSparseNDArray":
                "reference docstring defect: the example block reads a "
                "variable `dense` never defined in any example",
            "RowSparseNDArray.__setitem__":
                "reference docstring bug: calls mx.nd.row_sparse(), a "
                "function that does not exist in the reference either "
                "(the ctor is row_sparse_array)",
            ("divide", 11): "reference docstring typo: 'mx.nd.sprase'",
            ("divide", 12): "continues the typo'd example",
        }),
    "numpy/multiarray.py": dict(
        legacy=False, extra=None,
        skips=dict({
            "empty": "uninitialized-memory contents are arbitrary by "
                     "contract (this build zero-fills)",
            "empty_like": "same arbitrary-memory want as empty",
            "divide": "reference docstring defect: the single example "
                      "reads an undefined variable x",
            ("tanh", 0): "complex input: the reference raises TypeError, "
                         "this build computes it (superset)",
            ("tanh", 1): "malformed doctest: unmatched ')'",
            ("fabs", 1): "malformed doctest in the reference",
            ("expm1", 2): "reference docstring bug: shows np.exp "
                          "returning expm1's values",
            ("rint", 1): "reference docstring bug: claims rint(1.5)=1 "
                         "while rint(-1.5)=-2 — no rounding rule does "
                         "both; numpy/jax round-half-even gives 2",
            ("arcsinh", 1): "reference docstring bug: values are not "
                            "arcsinh of any plausible input",
            ("arcsinh", 2): "reference docstring bug: claims arcsinh(1)=0",
            "logspace": "reference docstring defect: examples read "
                        "undefined start/stop/num variables",
            ("tile", 9): "reference want carries a stray extra value",
            ("split", 2): "reference doc bug: copied numpy's arange(8) "
                          "example output against its own arange(9) input",
            ("array_split", 2): "same copied-output bug as split",
            ("max", 7): "reference kernel ignores NaN in max/min "
                        "reductions (kernel accident its doc enshrines); "
                        "this build follows numpy: NaN propagates",
            ("min", 7): "same NaN-ignoring kernel divergence",
            ("amax", 7): "same NaN-ignoring kernel divergence",
            ("amin", 7): "same NaN-ignoring kernel divergence",
            ("argmin", 8): "argmax/argmin over NaN: numpy returns the "
                           "NaN position, the reference kernel skips it",
            ("indices", 3): "reference doc copy-paste bug: grid[1] shown "
                            "with grid[0]'s row-index output",
            ("bitwise_and", 2): "reference doc bug: shows [26, 5] for "
                                "14&13, 3&13 (correct: [12, 1], as "
                                "numpy's own docs show)",
            "equal": "malformed doctest: unmatched ')' cascades",
            "not_equal": "malformed doctest: unmatched ')' cascades",
            "greater": "malformed doctest: unmatched ')' cascades",
            "less": "malformed doctest: unmatched ')' cascades",
            "greater_equal": "malformed doctest: unmatched ')' cascades",
            "less_equal": "malformed doctest: unmatched ')' cascades",
            ("hsplit", 6): "reference want merged with following "
                           "narrative by a missing blank line",
            ("may_share_memory", 2): "column slices are copies in this "
                                     "functional build (non-contiguous "
                                     "keys never alias) — documented "
                                     "redesign, so may_share_memory is "
                                     "honestly False",
            ("sum", 5): "sum(dtype=int32) on floats: numpy/jax cast the "
                        "input first (0.5->0), the reference kernel "
                        "accumulates in float then casts",
            ("pad", 11): "reference doc drops numpy's pad_with example "
                         "definition it then calls",
            ("pad", 12): "continues the undefined pad_with example",
            **{("einsum", i): "timing-narrative examples (ms figures "
                              "as wants)" for i in range(27, 60)},
        }),
    ),
    "numpy/linalg.py": dict(
        legacy=False, extra=_linalg_extra_globs,
        skips={
            "matrix_rank":
                "reference doc calls np.matrix_rank, which exists only "
                "under np.linalg in the reference too — the example "
                "cannot run there either",
            ("inv", 1): "reference doc shows LA.inv's output under the "
                        "preceding array-construction line",
            ("eigvals", 8): "eigenvalue order is unspecified; the values "
                            "match as a set ([-1, 1] vs [1, -1])",
            "eigvalsh": "malformed doctest: array literal continued "
                        "without '...' markers",
            "eig": "same malformed array-literal doctest",
            "eigh": "same malformed array-literal doctest",
        }),
    "numpy/random.py": dict(
        legacy=False, extra=None,
        skips={
            "weibull": "malformed doctest: '(' never closed",
            "pareto": "malformed doctest: '(' never closed",
            "power": "malformed doctest: '(' never closed",
        }),
    "initializer.py": dict(
        legacy=True, extra=None,
        skips={
            "register": "reference example decorates with a bare `alias` "
                        "name and calls block.initialize on a `block` "
                        "defined only in prose",
            "Mixed": "example references a `block` defined only in prose",
            "Zero": "example references a Module-API `module` object "
                    "defined only in prose",
            "One": "same prose-only `module` object",
            "Uniform": "same prose-only `module` object",
            "Normal": "same prose-only `module` object",
        }),
    "ndarray/random.py": dict(legacy=True, extra=None, skips={}),
    "ndarray/contrib.py": dict(
        legacy=True, extra=None,
        skips={
            ("rand_zipfian", 2):
                "reference docstring predates the *num_sampled factor "
                "its own code applies to expected_count_true "
                "(contrib.py:91: exp_count formula x4 vs doc 0.1245)",
            ("rand_zipfian", 3): "same stale expected-count figures",
            ("rand_zipfian", 4): "same stale expected-count figures",
        }),
    "util.py": dict(
        legacy=False, extra=None,
        skips={
            "set_np_shape":
                "documented redesign: this build is numpy-native, the "
                "shape flag defaults ON (util.py module docstring)",
            "is_np_shape": "same np-native default",
            "set_np": "same np-native default",
        }),
    "gluon/data/batchify.py": dict(
        legacy=False, extra=_batchify_extra_globs, skips={}),
    "symbol/symbol.py": dict(
        legacy=True, extra=None,
        skips={
            "Symbol.__neg__":
                "reference doc defects: the negation auto-name differs "
                "(_mulscalar vs negative) and later examples read a "
                "variable `b` no example defines",
            ("Symbol.list_arguments", 3):
                "reference doc defect: references the method without "
                "parentheses yet shows the call's result",
            ("Symbol.debug_str", 5):
                "debug_str emits this build's own dump format (node "
                "order/attr layout differ; content equivalent)",
        }),
    "gluon/metric.py": dict(
        legacy=False, extra=None,
        skips={
            "CompositeEvalMetric":
                "malformed doctest in the reference: for-loop body "
                "continued without '...' markers; subsequent examples "
                "are its orphaned continuation lines",
            ("TopKAccuracy", 6):
                "reference docstring predates the '_%d' name suffix its "
                "own __init__ appends (reference metric.py:472)",
            "MCC": "malformed doctest: array literals continued without "
                   "'...' markers ('(' never closed), cascading into "
                   "every later example of the block",
            "PCC": "same malformed array-literal doctest as MCC",
        }),
}


@pytest.mark.parametrize("relpath", sorted(FILES))
def test_reference_docstring(relpath):
    """One case per reference source file (the case list is static, so
    every xdist worker collects the same tests); the file is read inside
    the test, which skips when the reference tree is not on this machine.
    Every docstring of the file runs; failures are reported together."""
    try:
        blocks = collect_blocks(relpath)
    except OSError as e:
        pytest.skip(f"reference source tree not available: {e}")
    cfg = FILES[relpath]
    skips = cfg["skips"]
    failures = []
    for qualname, examples in blocks:
        if qualname in skips:
            continue
        skip_idx = {idx for (qn, idx) in
                    [k for k in skips if isinstance(k, tuple)]
                    if qn == qualname}
        globs = default_globs()
        if cfg["extra"] is not None:
            globs.update(cfg["extra"]())
        reset_mode(cfg["legacy"])
        try:
            run_block(examples, globs, skip_idx=skip_idx)
        except ExampleFailure as e:
            failures.append(f"{relpath}::{qualname}: {e}")
        finally:
            reset_mode(legacy=False)
    assert not failures, "\n".join(failures)
