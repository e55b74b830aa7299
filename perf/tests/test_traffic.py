"""The generator: the same seed gives the same requests, and every seed
the same set of sizes in an order of its own."""
import tiny
import traffic


def _pairs(reqs):
    return [(len(r.prompt), r.max_new) for r in reqs]


def test_same_seed_same_requests():
    mix = tiny.serve_mix()
    a = traffic.closed_loop_pool(mix, 512, 2**31 + 9)
    b = traffic.closed_loop_pool(mix, 512, 2**31 + 9)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert _pairs(a) == _pairs(b)
    assert [r.index for r in a] == list(range(32))


def test_every_seed_the_same_set_in_an_order_of_its_own():
    mix = dict(tiny.serve_mix(), pool_size=64)
    a = traffic.closed_loop_pool(mix, 512, 10)
    b = traffic.closed_loop_pool(mix, 512, 2**31 + 11)
    for k in (0, 1):       # the prompts' set and the outputs' set
        assert sorted(p[k] for p in _pairs(a)) == \
            sorted(p[k] for p in _pairs(b))
    assert _pairs(a) != _pairs(b)
    # the whole order is the seed's: requests move across the pool, not
    # only between neighbours
    where = {}
    for i, r in enumerate(a):
        where.setdefault(len(r.prompt), []).append(i)
    moved = [abs(i - where[len(r.prompt)][0]) for i, r in enumerate(b)
             if len(where[len(r.prompt)]) == 1]
    assert max(moved) > 16
    assert a[0].prompt != b[0].prompt


def test_lengths_are_quantiles_within_the_clip():
    spec = {'median': 192, 'sigma': 0.8, 'min': 32, 'max': 1024}
    lens = traffic.quantile_lengths(spec, 101)
    assert lens.min() >= 32 and lens.max() <= 1024
    assert lens[50] == 192 and list(lens) == sorted(lens)


def test_train_rows_all_differ():
    rows = traffic.train_tokens(512, 3, 8, 64)
    assert rows.shape == (8, 65)
    assert len({tuple(r) for r in rows.tolist()}) == 8
    assert (rows == traffic.train_tokens(512, 3, 8, 64)).all()
