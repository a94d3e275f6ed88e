"""The benchmark's traffic generator: seeded schedules, wall-clock due
times, the stated length distributions, prefix hits on a reduced engine,
and the closed loop's outstanding count."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator as G

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


class Req:
    """Stands in for the program's Request."""

    def __init__(self, uid, prompt, max_new_tokens):
        self.uid, self.prompt, self.max_new_tokens = uid, prompt, max_new_tokens
        self.output = []


def _mix(name, **kw):
    mix = json.loads((TRAFFIC / f"{name}.json").read_text())
    mix.update(kw)
    return mix


def _gen(mix, seed, horizon=30.0, slots=8, vocab=32064, max_len=1024):
    return G.load(mix)(mix, seed=seed, vocab=vocab, max_len=max_len,
                       slots=slots, horizon_s=horizon, request_cls=Req)


def _serve(pending, t_seen):
    """Pretend the server answered with max_new tokens at t_seen."""
    p = pending
    p.req.output = list(range(p.req.max_new_tokens))
    return (p, t_seen)


def _drive_sessions(gen, until=20.0, service=0.3):
    """A server that answers every request ``service`` s after it is due."""
    seen, t, open_ = [], 0.0, []
    while t < until:
        fin = [_serve(p, p.due + service) for p in open_
               if p.due + service <= t]
        open_ = [p for p in open_ if p.due + service > t]
        new = gen.poll(t, fin)
        seen.extend(new)
        open_.extend(new)
        t += 0.05
    return seen


def test_same_seed_same_schedule_and_tokens():
    mix = _mix("chat-shared")
    a = _drive_sessions(_gen(mix, 2 ** 33 + 5))
    b = _drive_sessions(_gen(mix, 2 ** 33 + 5))
    c = _drive_sessions(_gen(mix, 6))
    key = lambda ps: [(round(p.due, 9), p.req.prompt, p.req.max_new_tokens)
                      for p in ps]
    assert key(a) == key(b)
    assert key(a) != key(c)


def test_due_times_are_wall_clock_seconds():
    mix = _mix("chat-shared", sessions_per_s=2.0)
    gen = _gen(mix, 11, horizon=400.0)
    firsts = gen.arrivals
    gaps = np.diff([0.0] + firsts)
    assert gaps.mean() == pytest.approx(0.5, rel=0.1)     # 2 sessions/s
    seen = _drive_sessions(gen, until=60.0, service=0.3)
    later = [p for p in seen if p.turn > 0]
    assert later
    for p in later:
        # due a think time after the previous reply was seen
        prev = [q for q in seen if q.session == p.session
                and q.turn == p.turn - 1][0]
        think = gen.sessions[p.session]["think"][p.turn - 1]
        assert p.due == pytest.approx(prev.due + 0.3 + think)
        assert p.req.prompt[:len(prev.req.prompt)] == prev.req.prompt


def test_lengths_follow_the_mix_and_do_not_depend_on_the_seed():
    mix = _mix("chat-shared")
    g1, g2 = _gen(mix, 1, horizon=600.0), _gen(mix, 2, horizon=600.0)
    msgs = lambda g: sorted(int(x) for s in g.sessions for x in s["msg"])
    assert msgs(g1) == msgs(g2)
    m = np.array(msgs(g1))
    spec = mix["message"]
    assert m.min() >= spec["min"] and m.max() <= spec["max"]
    assert np.median(m) == pytest.approx(spec["median"], rel=0.1)
    turns = [s["turns"] for s in g1.sessions]
    assert min(turns) == mix["turns"]["min"]
    assert max(turns) == mix["turns"]["max"]
    which = np.bincount([s["system"] for s in g1.sessions])
    assert which[0] > which[1] > which[3]             # Zipf over 4 prompts
    batch = _mix("batch-reasoning", requests=2000)
    gb = _gen(batch, 3)
    outs = np.array([o for _, o in gb.jobs])
    assert np.median(outs) == pytest.approx(batch["output"]["median"],
                                            rel=0.1)
    assert outs.min() >= 256 and outs.max() <= 2048


def test_prompts_share_only_the_system_prompts():
    mix = _mix("chat-shared")
    gen = _gen(mix, 4)
    firsts = [p for p in _drive_sessions(gen, until=15.0) if p.turn == 0]
    L = mix["system"]["length"]
    for p in firsts:
        assert p.req.prompt[:L] in gen.system_prompts
    starts = [p.req.prompt[L] for p in firsts]
    assert len(set(starts)) == len(starts)       # distinct first tokens
    for p in firsts:
        assert len(p.req.prompt) + p.req.max_new_tokens <= 1024


def test_closed_loop_keeps_two_requests_per_slot_outstanding():
    gen = _gen(_mix("batch-reasoning"), 9, slots=8, vocab=152064,
               max_len=2560)
    first = gen.poll(0.0)
    assert len(first) == 16
    assert gen.poll(1.0) == []
    again = gen.poll(2.0, [(first[0], 2.0), (first[3], 2.0)])
    assert len(again) == 2 and all(p.due == 2.0 for p in again)
    assert len({p.req.prompt[0] for p in first + again}) == 18


def test_prefill_buckets():
    chat = _gen(_mix("chat-shared"), 1)
    assert G.prefill_buckets(*chat.prefill_bounds(), 1024) == [
        16, 32, 64, 128, 256, 512, 1024]
    batch = _gen(_mix("batch-reasoning"), 1, vocab=152064, max_len=2560)
    assert G.prefill_buckets(*batch.prefill_bounds(), 2560) == [
        64, 128, 256, 512]


def test_chat_sessions_hit_the_prefix_cache_of_a_reduced_engine():
    import jax
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import PagedEngine, Request

    cfg = reduced(get_config("phi3-mini-3.8b"), num_layers=1)
    model = build_model(cfg, max_seq=128)
    eng = PagedEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                      max_len=128, page_size=8, ticks_per_sync=4,
                      record_traffic=False)
    mix = _mix("chat-shared", system={"count": 2, "length": 20,
                                      "zipf_s": 1.1},
               message={"median": 6, "sigma": 0.5, "min": 3, "max": 12},
               output={"median": 4, "sigma": 0.5, "min": 2, "max": 6},
               sessions_per_s=4.0, think_s=0.05)
    gen = G.load(mix)(mix, seed=5, vocab=cfg.vocab_size, max_len=128,
                      slots=2, horizon_s=3.0, request_cls=Request)
    t, inflight, served = 0.0, [], 0
    while t < 3.0:
        fin = [(p, t) for p in inflight if p.req.terminal]
        inflight = [p for p in inflight if not p.req.terminal]
        served += len(fin)
        for p in gen.poll(t, fin):
            eng.submit(p.req)
            inflight.append(p)
        eng.step()
        t += 0.1
    st = eng.paged_stats()
    assert served > 4
    assert st["prefix_hits"] > 0 and st["prefix_hit_rate"] > 0.3


def test_the_seed_changes_tokens_but_not_sizes_or_times():
    mix = _mix("chat-shared")
    short, a, b = (_gen(mix, 1, horizon=30.0), _gen(mix, 1, horizon=90.0),
                   _gen(mix, 2 ** 33 + 9, horizon=90.0))
    shape = lambda g: [(s["turns"], s["msg"].tolist(), s["out"].tolist(),
                        s["think"].tolist(), s["system"]) for s in g.sessions]
    assert a.arrivals == b.arrivals and shape(a) == shape(b)
    # a longer window adds sessions after the shorter one's, in order
    n = len(short.sessions)
    assert short.arrivals == a.arrivals[:n] and shape(short) == shape(a)[:n]
    assert a.system_prompts != b.system_prompts
    closed = _mix("batch-reasoning", requests=64)
    assert _gen(closed, 1).jobs == _gen(closed, 7).jobs


def test_a_mix_without_system_prompts_shares_no_prefix():
    mix = _mix("chat-shared", system={"count": 0, "length": 256,
                                      "zipf_s": 1.1},
               turns={"min": 1, "max": 1}, sessions_per_s=2.0)
    gen = _gen(mix, 3)
    assert gen.system_prompts == [] and gen.sessions
    seen = _drive_sessions(gen, until=20.0)
    assert seen and all(p.turn == 0 for p in seen)
    starts = [p.req.prompt[0] for p in seen]
    assert len(set(starts)) == len(starts)
    for p in seen:
        n = len(p.req.prompt)
        assert mix["message"]["min"] <= n <= mix["message"]["max"]


def test_a_generator_added_as_a_file_is_found_by_its_mix(tmp_path):
    d = tmp_path / "bench" / "traffic"
    d.mkdir(parents=True)
    (d / "steady.py").write_text(
        "from bench.generator import Pending, Traffic\n"
        "class Generator(Traffic):\n"
        "    def poll(self, now, finished=()):\n"
        "        return [Pending(self.request(self.tokens(4), 2), now)]\n")
    mix = {"generator": "steady", "shape_seed": 1}
    cls = G.load(mix, tmp_path)
    gen = cls(mix, seed=1, vocab=64, max_len=16, slots=1, horizon_s=1.0,
              request_cls=Req)
    (p,) = gen.poll(0.5)
    assert p.due == 0.5 and len(p.req.prompt) == 4
    with pytest.raises(FileNotFoundError, match="nowhere"):
        G.load({"generator": "nowhere"}, tmp_path)
