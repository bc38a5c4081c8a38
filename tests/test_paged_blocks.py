"""Attention over float32 pages without the kernel (PR 38): a prefill
chunk, a verify block, a decode step the paged kernel does not cover.
``decode_attention._xla_blocks`` walks the pages some query of the call
can see, a block a turn, with running max and sum; here it stands against
a whole-softmax reference written over each row's logical sequence, its
trip count against the host's formula (which the engine counts a chunk's
visited keys with: ``tests/test_host_spans.py``, ``tests/test_mellum.py``).

The pools are filled as the writes would have left them: a row's logical
page ``j`` in its table's column ``j`` (a full layer) or ``j mod N`` (a
ring, the newest page standing where the one ``N`` before it stood).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.pallas import decode_attention as da

PAGE, D, KV = 32, 16, 2
N_FULL = 12                     # a full table of 384 keys
WINDOW, N_RING = 64, 8          # 8 x 32 hold 64 + 127 positions and a page


@pytest.fixture(autouse=True)
def routing(monkeypatch):
    """No interpreter, so that a decode-shaped call comes here too; and a
    block of four of these pages, so that the loops take several turns."""
    da.use_interpret(False)
    da.force_path(None)
    da.reset_fallbacks()
    monkeypatch.setattr(da, "_BLOCK_KEYS", 4 * PAGE)
    yield
    da.force_path(None)


def _case(seed, heads, t, starts, ring, dead=()):
    """Queries, pools, table and each row's logical K/V for rows starting
    at ``starts``; a ``dead`` row's table is all null and it reads the
    null page, which holds zeros."""
    rs = np.random.RandomState(seed)
    n = N_RING if ring else N_FULL
    b = len(starts)
    table = (1 + rs.permutation(b * n).reshape(b, n)).astype(np.int32)
    table[list(dead)] = 0
    pools = np.zeros((2, b * n + 1, KV, PAGE, D), np.float32)
    seqs = []
    for r, sp in enumerate(starts):
        length = sp + t if ring else min(sp + t, n * PAGE)
        seq = rs.randn(2, KV, length, D).astype(np.float32)
        if r in dead:
            seq[:] = 0
        seqs.append(seq)
        for j in range(-(-length // PAGE)):      # oldest first: a ring's
            rows = seq[:, :, j * PAGE:(j + 1) * PAGE]    # newest stays
            col = j % n if ring else j
            pools[:, table[r, col], :, :rows.shape[2]] = rows
    pools[:, 0] = 0
    q = rs.randn(b, heads, t, D).astype(np.float32)
    return q, pools[0], pools[1], table, seqs


def _whole(q, seqs, starts, window):
    """Softmax over every visible key at once, a query at a time; NaN
    where a query lies past its row's sequence (padding past the table's
    extent: nothing is asked of it)."""
    b, h, t, d = q.shape
    g = h // KV
    out = np.full(q.shape, np.nan, np.float64)
    for r in range(b):
        k, v = seqs[r].astype(np.float64)
        for i in range(t):
            pos = starts[r] + i
            if pos >= k.shape[1]:
                continue
            lo = 0 if window is None else max(pos - window + 1, 0)
            for head in range(h):
                s = k[head // g, lo:pos + 1] @ q[r, head, i] * d ** -0.5
                w = np.exp(s - s.max())
                out[r, head, i] = (w / w.sum()) @ v[head // g, lo:pos + 1]
    return out


def _blocks(q, k, v, table, starts, window, jit=True):
    fn = lambda *a: da.paged_decode_attention(*a, window=window)  # noqa: E731
    args = [jnp.asarray(a) for a in (q, k, v, table,
                                     np.asarray(starts, np.int32))]
    return np.asarray((jax.jit(fn) if jit else fn)(*args))


def _agree(got, want):
    asked = ~np.isnan(want)
    assert asked.any() and np.isfinite(got).all()
    np.testing.assert_allclose(got[asked], want[asked], rtol=2e-5, atol=2e-5)


# rows at unlike positions: the first position, the middle of a page, far
# on (on a ring: a chunk astride the window's edge, and one after the ring
# has wrapped), and a dead row
STARTS = {False: [0, 37, 250, 0], True: [0, 62, 700, 0]}


@pytest.mark.parametrize("group", [4, 16])
@pytest.mark.parametrize("t", [1, 5, 128])
@pytest.mark.parametrize("ring", [False, True], ids=["full", "ring"])
def test_blocks_match_the_whole_softmax(ring, t, group):
    window = WINDOW if ring else None
    starts = STARTS[ring]
    q, k, v, table, seqs = _case(t + group + ring, KV * group, t, starts,
                                 ring, dead=(3,))
    got = _blocks(q, k, v, table, starts, window)
    assert da.last_path() == "xla_blocks"
    # a decode-shaped call that the kernel did not serve is counted
    assert da.fallback_count() == (1 if t == 1 else 0)
    _agree(got, _whole(q, seqs, starts, window))
    assert np.all(got[3] == 0.0)          # the null page's zeros


@pytest.mark.parametrize("ring,starts,t", [
    (True, [60], 8),            # queries 60..67 astride the window's edge
    (True, [63, 64], 1),        # the last to see key 0, the first not to
    (True, [256], 32),          # the first page of the ring's second lap
    (True, [992], 128),         # wrapped three times, a whole chunk
    (False, [300], 128),        # the last chunk: 44 positions of padding
    (False, [383], 1),          # the table's last key
], ids=["edge", "edge_decode", "second_lap", "wrapped", "padding", "last"])
def test_blocks_at_the_edges(ring, starts, t):
    window = WINDOW if ring else None
    q, k, v, table, seqs = _case(len(starts) + t, 8, t, starts, ring)
    _agree(_blocks(q, k, v, table, starts, window),
           _whole(q, seqs, starts, window))


@pytest.mark.parametrize("ring,starts,t,turns", [
    (False, [0], 128, 1),           # keys 0..127: pages 0..3 of 4 a turn
    (False, [130], 128, 3),         # to key 257: page 8
    (False, [300], 128, 3),         # held to the table's 12 pages
    (False, [0, 37, 250, 0], 5, 2),     # the union: to key 254
    (True, [0], 128, 1),
    (True, [992], 128, 2),          # pages 29..34 in turns of 4
    (True, [0, 62, 700, 0], 5, 6),      # the union: pages 0..22
], ids=["first", "third", "extent", "union", "ring_first", "ring_wrapped",
        "ring_union"])
def test_the_loop_runs_as_often_as_the_host_reckons(monkeypatch, ring,
                                                    starts, t, turns):
    """``block_range`` on the host's numbers is what the scheduler counts
    ``kv_keys_visited`` with; the loop takes its bounds from the same
    function on traced ones."""
    window, n = (WINDOW, N_RING) if ring else (None, N_FULL)
    assert da.block_range(np.asarray(starts), t, PAGE, n,
                          window)[1:] == (turns, 4)
    ran, loop = [], jax.lax.fori_loop

    def counting(lower, upper, body, init):
        ran.append(int(upper) - int(lower))
        return loop(lower, upper, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", counting)
    q, k, v, table, seqs = _case(turns, 8, t, starts, ring)
    got = _blocks(q, k, v, table, starts, window, jit=False)
    assert ran == [turns]
    _agree(got, _whole(q, seqs, starts, window))


def test_a_table_of_one_block_takes_no_loop(monkeypatch):
    """At the width it is served with, this table of 384 keys is one
    block (Falcon-H1's of 512 is): one turn, straight through."""
    monkeypatch.setattr(da, "_BLOCK_KEYS", 512)
    monkeypatch.setattr(jax.lax, "fori_loop", None)
    starts = STARTS[False]
    q, k, v, table, seqs = _case(9, 8, 5, starts, False, dead=(3,))
    _agree(_blocks(q, k, v, table, starts, None, jit=False),
           _whole(q, seqs, starts, None))


@pytest.mark.parametrize("page,n_pages,window,t,c", [
    (128, 56, None, 128, 4),        # Command A+: 14 turns of 512 keys
    (128, 33, 4096, 128, 4),        # its ring: 9 turns hold 33 pages
    (128, 9, 1024, 128, 3),         # Mellum-2's ring: 3 even turns
    (128, 4, None, 128, 4),         # Falcon-H1: the table is one block
    (16, 4, None, 16, 4),           # a table under a block
    (192, 2, None, 1, 2),           # two pages of 192 are under a block
    (1024, 3, None, 1, 1),          # a page over a block
])
def test_a_blocks_pages_divide_what_a_row_can_see(monkeypatch, page, n_pages,
                                                  window, t, c):
    monkeypatch.setattr(da, "_BLOCK_KEYS", 512)     # as it is served
    assert da.block_range(np.asarray([0]), t, page, n_pages, window)[2] == c


def test_int8_pages_still_gather_their_rings():
    """Scales ride with their pages: the one route that gathers a table's
    every column, which no cell runs."""
    rs = np.random.RandomState(0)
    table = (1 + rs.permutation(8).reshape(2, 4)).astype(np.int32)
    k, v = (rs.randint(-127, 128, (9, KV, PAGE, D)).astype(np.int8)
            for _ in "kv")
    ks, vs = (rs.uniform(0.01, 0.1, (9, KV, PAGE)).astype(np.float32)
              for _ in "kv")
    q = rs.randn(2, 8, 5, D).astype(np.float32)
    got = da.paged_decode_attention(
        *map(jnp.asarray, (q, k, v, table, np.asarray([3, 90], np.int32))),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    assert da.last_path() == "xla" and np.isfinite(np.asarray(got)).all()


def test_force_pallas_refuses_what_the_paged_kernel_cannot_serve():
    q, k, v, table, _ = _case(0, 8, 5, [3], False)
    da.force_path("pallas")
    with pytest.raises(ValueError, match="unsupported paged shape"):
        _blocks(q, k, v, table, [3], None, jit=False)
