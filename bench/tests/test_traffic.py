"""Every seed gets the same work, in another order."""
import traffic

MIX = {"arrivals": "open_loop", "rate_per_s": 12.0, "arrival_seed": 5,
       "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                  "min": 32, "max": 1024},
       "output": {"dist": "exponential", "mean": 58.45, "min": 1,
                  "max": 1024},
       "block": 64}


def _shape(reqs):
    return sorted((len(r.prompt), r.out) for r in reqs)


def test_open_loop_same_sizes_other_order_for_every_seed():
    a = traffic.open_loop(MIX, 1, 30.0, 49152)
    b = traffic.open_loop(MIX, 2 ** 31 + 77, 30.0, 49152)
    assert _shape(a) == _shape(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # one fixed sample path of arrivals, not rescaled to the window
    assert [r.due for r in a] == [r.due for r in b]
    assert all(0 < x.due < y.due < 30.0 for x, y in zip(a, a[1:]))
    assert abs(len(a) - 360) < 3 * 360 ** 0.5
    # a shorter window holds the same path's first arrivals
    short = traffic.open_loop(MIX, 1, 10.0, 49152)
    assert [r.due for r in short] == [r.due for r in a][:len(short)]
    assert a == traffic.open_loop(MIX, 1, 30.0, 49152)


def test_backlog_blocks_hold_the_same_work():
    a = traffic.backlog_block(MIX, 5, 0, 49152, 0)
    b = traffic.backlog_block(MIX, 5, 1, 49152, 64)
    assert _shape(a) == _shape(b)
    assert [r.rid for r in b] == list(range(64, 128))


def test_lengths_clip_to_the_mix():
    n = traffic.lengths(MIX["prompt"], 1000)
    assert n.min() >= 32 and n.max() <= 1024
    assert abs(sorted(n)[500] - 256) <= 2


def test_exponential_lengths_keep_the_mean():
    n = traffic.lengths(MIX["output"], 1000)
    assert n.min() == 1 and n.max() < 1024
    assert abs(n.mean() - 58.45) < 0.5


def test_warm_prompts_cover_each_bucket_the_mix_hits_once():
    buckets = (8, 16, 32, 64, 128, 256, 512, 1024, 1280)
    lens = [len(p) for p in traffic.warm_prompts(MIX, 49152, buckets, 30.0)]
    assert lens == [32, 64, 128, 256, 512, 1024]
    short = dict(MIX, prompt={"dist": "exponential", "mean": 19.31,
                              "min": 1, "max": 256})
    lens = [len(p) for p in traffic.warm_prompts(short, 49152, buckets,
                                                 30.0)]
    assert lens[:-1] == [8, 16, 32, 64] and 64 < lens[-1] <= 128
