"""Traffic generators: the same multiset of sizes for every seed, a stated
median, a backlog that fits the engine."""
import numpy as np
import pytest

from bench import traffic


def drain(src, until=1e9):
    out = []
    while src.next_due() is not None and src.next_due() <= until:
        out.extend(src.pop_due(src.next_due()))
    return out


def test_independent_sizes_do_not_depend_on_the_seed():
    spec = traffic.load("chat-backlog")
    a = drain(traffic.make(spec, seed=1, vocab=1000, max_len=16384))
    b = drain(traffic.make(spec, seed=2**40 + 9, vocab=1000, max_len=16384))
    # below the clip at max_len - max_new, which depends on the pairing
    short = lambda xs: sorted(len(x.prompt) for x in xs if len(x.prompt) < 12000)
    assert short(a) == short(b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    lens = np.asarray([len(x.prompt) for x in a])
    assert abs(np.median(lens) / 1730 - 1) < 0.1


@pytest.mark.parametrize("mix,max_len", [("longdoc-batch", 32768), ("chat-backlog", 16384)])
def test_backlog_is_due_at_once_and_fits(mix, max_len):
    spec = traffic.load(mix)
    a = drain(traffic.make(spec, seed=3, vocab=1000, max_len=max_len))
    assert len(a) == spec["arrival"]["count"] and all(x.due == 0 for x in a)
    assert all(len(x.prompt) + x.max_new <= max_len for x in a)


@pytest.mark.parametrize("kind", ["bitrev", "golden"])
def test_banded_order_spreads_every_stretch(kind):
    vals = np.arange(64.0)
    order = traffic.band_order(kind, 16)
    assert sorted(order) == list(range(16))
    a = traffic.banded_order(vals, np.random.default_rng(1), 16, order)
    b = traffic.banded_order(vals, np.random.default_rng(2), 16, order)
    assert sorted(a) == sorted(vals) and list(a) != list(b)
    assert list(a // 4) == list(b // 4) == list(np.resize(order, 64))  # same bands
    for j in range(0, 64, 16):  # each stretch of 16 visits every band once
        assert sorted(a[j:j + 16] // 4) == list(range(16))
    assert np.all(np.abs(a - b) <= 3)  # seeds differ only within a band
