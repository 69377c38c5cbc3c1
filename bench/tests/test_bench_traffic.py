"""The traffic generators: deterministic per seed, the same work for every
seed, and arrivals on a fixed open-loop schedule."""
import numpy as np

from bench import harness, traffic

CHAT = harness.load_json(harness.ROOT / "bench" / "traffic" /
                         "serve.chat.json")


def test_token_feed_is_deterministic_and_rows_differ():
    kw = dict(nodes=4, batch_per_node=2, seq_len=64, vocab=500, n_groups=8,
              hetero=0.7)
    a = traffic.TokenFeed(seed=2**31 + 7, **kw)
    b = traffic.TokenFeed(seed=2**31 + 7, **kw)
    for t in range(3):
        x, y = a.batch(t), b.batch(t)
        assert all(np.array_equal(x[k], y[k]) for k in x)
        assert x["tokens"].shape == (4, 2, 64)
        assert x["group_ids"].max() < 8 and x["tokens"].max() < 500
    rows = np.concatenate([a.batch(t)["tokens"].reshape(-1, 64)
                           for t in range(3)])
    assert len({r.tobytes() for r in rows}) == len(rows)
    c = traffic.TokenFeed(seed=11, **kw)
    assert not np.array_equal(c.batch(0)["tokens"], a.batch(0)["tokens"])


def test_request_mix_same_work_every_seed():
    mixes = [traffic.request_mix(CHAT, 30.0, s, 49155)
             for s in (1, 2, 2**31 + 3)]
    n = round(CHAT["rate_per_s"] * 30)
    for m in mixes:
        assert len(m) == n
    keys = [(sorted(len(r.prompt) for r in m),
             sorted(r.max_new_tokens for r in m)) for m in mixes]
    assert keys[0] == keys[1] == keys[2]
    assert [len(r.prompt) for r in mixes[0]] != \
        [len(r.prompt) for r in mixes[1]]
    again = traffic.request_mix(CHAT, 30.0, 2, 49155)
    assert [(r.arrival, r.prompt, r.max_new_tokens) for r in again] == \
        [(r.arrival, r.prompt, r.max_new_tokens) for r in mixes[1]]


def test_arrivals_form_an_open_loop_schedule():
    m = traffic.request_mix(CHAT, 30.0, 5, 49155)
    due = np.array([r.arrival for r in m])
    assert np.all(np.diff(due) > 0) and due[0] > 0 and due[-1] < 30.0
    # the mean gap is the offered rate's
    assert abs(np.diff(due).mean() * CHAT["rate_per_s"] - 1.0) < 0.05
    p, o = CHAT["prompt"], CHAT["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in m)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in m)
    assert abs(np.median([len(r.prompt) for r in m]) - p["median"]) \
        < 0.1 * p["median"]


def test_prompt_page_counts_cover_the_mix():
    m = traffic.request_mix(CHAT, 30.0, 9, 49155)
    used = {traffic.pages_for(len(r.prompt), CHAT["page_size"]) for r in m}
    assert used == set(traffic.prompt_page_counts(CHAT, 30.0))


def test_percentile_nearest_rank():
    assert traffic.percentile(list(range(1, 101)), 95) == 95.0
    assert traffic.percentile([3.0], 95) == 3.0
