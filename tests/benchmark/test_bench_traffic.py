"""The traffic generator: seeded, within its clips, near its medians, and
the same work for every seed."""

import collections
import statistics

import pytest

from bench import spec, traffic


def mix(name):
    return spec.load_json(spec.ROOT / "bench" / "traffic" / f"{name}.json")


def chat():
    return dict(mix("chat"), rate_rps=2.5)


def test_same_seed_same_requests():
    a = traffic.open_loop(chat(), 45, spec.rng(2**31 + 5, "traffic"))
    b = traffic.open_loop(chat(), 45, spec.rng(2**31 + 5, "traffic"))
    assert a == b
    c = traffic.backlog(mix("decode"), 100, spec.rng(9, "traffic"))
    assert c == traffic.backlog(mix("decode"), 100, spec.rng(9, "traffic"))


def test_seeds_change_order_not_work():
    a = traffic.open_loop(chat(), 45, spec.rng(1, "traffic"))
    b = traffic.open_loop(chat(), 45, spec.rng(2, "traffic"))
    assert a != b
    assert sorted(x.prompt_len for x in a) == sorted(x.prompt_len for x in b)
    assert sorted(x.output_len for x in a) == sorted(x.output_len for x in b)
    gaps = [sorted(round(t - s, 9) for s, t in zip([0] + [x.due for x in r],
                                                   [x.due for x in r]))
            for r in (a, b)]
    assert gaps[0] == gaps[1]
    # a backlog's every whole block holds the same lengths for every seed
    block = mix("decode")["block"]
    c = traffic.backlog(mix("decode"), 3 * block, spec.rng(1, "traffic"))
    d = traffic.backlog(mix("decode"), 3 * block, spec.rng(2, "traffic"))
    for k in range(3):
        sl = slice(k * block, (k + 1) * block)
        assert (collections.Counter(x.output_len for x in c[sl])
                == collections.Counter(x.output_len for x in d[sl]))


@pytest.mark.parametrize("name", ["chat", "decode"])
def test_lengths_within_clips_and_medians_near_targets(name):
    m = chat() if name == "chat" else mix(name)
    reqs = (traffic.open_loop(m, 400, spec.rng(3, "traffic")) if name == "chat"
            else traffic.backlog(m, 640, spec.rng(3, "traffic")))
    for key, attr in (("prompt", "prompt_len"), ("output", "output_len")):
        d = m[key]
        xs = [getattr(r, attr) for r in reqs]
        assert min(xs) >= d["min"] and max(xs) <= d["max"]
        assert abs(statistics.median(xs) - d["median"]) <= 0.05 * d["median"] + 1


def test_open_loop_rate_and_window():
    reqs = traffic.open_loop(chat(), 45, spec.rng(4, "traffic"))
    assert abs(len(reqs) - 2.5 * 45) <= 2
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 45


def test_bursty_arrivals_come_in_groups():
    m = dict(chat(), arrivals="bursty", burst=8)
    reqs = traffic.open_loop(m, 200, spec.rng(5, "traffic"))
    same_instant = collections.Counter(r.due for r in reqs)
    assert max(same_instant.values()) > 8
    assert abs(len(reqs) - 2.5 * 200) <= 0.2 * 2.5 * 200


def test_seed32_takes_any_whole_number():
    seeds = {spec.seed32(s, "weights") for s in (0, 1, 2**31 - 1, 2**31 + 1,
                                                 2**40 + 3, -5)}
    assert len(seeds) == 6 and all(0 <= s < 2**31 for s in seeds)
