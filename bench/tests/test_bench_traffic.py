"""The traffic tape and the query schedule."""
import numpy as np

from rbench import datagen
from rbench.traffic import ADD, DELETE, FEATURE, Tape, poisson_times

from repro.core.graph import EdgeUpdate

TRAFFIC = dict(tenants=4, tenant_skew=1.0, vertex_skew=0.0, chunk=50,
               mix=[1, 1, 1], max_batch=50, readd_after=50)


def _tape(seed, n=300, m=4000, holdout=0.5, **kw):
    cfg = dict(graph="er", n=n, m=m, holdout_frac=holdout)
    ss_g, ss_t = datagen.seeds(seed, 2)
    snap, hold = datagen.make_graph(cfg, ss_g)
    x0 = np.zeros((n, 8), np.float32)
    return Tape(n, snap, hold, x0, dict(TRAFFIC, **kw), ss_t), snap


def _flat(ups):
    return [(type(u).__name__, u.src, u.dst, u.add) if isinstance(u, EdgeUpdate)
            else ("F", u.vertex, tuple(u.value.tolist())) for u in ups]


def test_same_seed_same_tape():
    a, _ = _tape(2**31 + 7)
    b, _ = _tape(2**31 + 7)
    c, _ = _tape(2**31 + 8)
    ta = [(t, _flat(u)) for t, u in (a.next_chunk() for _ in range(40))]
    tb = [(t, _flat(u)) for t, u in (b.next_chunk() for _ in range(40))]
    tc = [(t, _flat(u)) for t, u in (c.next_chunk() for _ in range(40))]
    assert ta == tb
    assert ta != tc


def test_mix_holds_after_the_holdout_runs_out():
    """The holdout (~2,000 edges) is spent thrice over: deleted edges
    are added back, every add and delete is valid in tape order, and the
    kinds keep the stated 1:1:1 in the second half of the tape."""
    tape, (s, d) = _tape(5)
    present = set(zip(s.tolist(), d.tolist()))
    kinds = []
    for _ in range(400):                           # 20,000 updates
        _, ups = tape.next_chunk()
        for u in ups:
            if isinstance(u, EdgeUpdate):
                key = (u.src, u.dst)
                assert (key in present) != u.add
                (present.add if u.add else present.discard)(key)
                kinds.append(ADD if u.add else DELETE)
            else:
                kinds.append(FEATURE)
    late = np.bincount(kinds[len(kinds) // 2:], minlength=3) / (len(kinds) / 2)
    assert np.allclose(late, 1 / 3, atol=0.03), late
    adds = kinds.count(ADD)
    assert adds > 3 * 2000
    fs, fd = tape.final_edges()
    assert set(zip(fs.tolist(), fd.tolist())) == present


def test_open_loop_rate():
    """The Poisson schedule (snapshot queries) keeps its stated mean rate."""
    rng = np.random.default_rng(3)
    times = poisson_times(500.0, rng)
    last = [next(times) for _ in range(20000)][-1]
    rate = 20000 / last
    # 20,000 arrivals: the mean rate's relative sd is 1/sqrt(20000) = 0.7%
    assert abs(rate / 500.0 - 1) < 0.03, rate
