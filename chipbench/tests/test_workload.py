"""The traffic generator: seeded tokens, the same work and timing for
every seed, bucketed and capped prompts, and the arrival segments."""
import json

import numpy as np
import pytest

from chipbench import harness, workload

MIX = json.loads((harness.ROOT / "chipbench" / "traffic" / "agent.json")
                 .read_text())
BIG_SEED = 2 ** 31 + 12345


def _flat(wfs):
    return [s for wf in wfs for s in wf.steps]


def _serve(wfs, seed=0):
    """Build every prompt as the serve loop does, each step serving
    ``out`` made-up tokens; returns each step's tokens, prompt and
    output."""
    rng = np.random.default_rng(seed)
    served = {}
    for wf in wfs:
        for s in wf.steps:
            prompt = workload.build_prompt(
                s, [served[wf.wid, p] for p in s.parents])
            served[wf.wid, s.idx] = np.concatenate(
                [prompt, rng.integers(0, 2 ** 15, s.out, np.int32)])
    return served


def test_same_seed_same_inputs():
    a = workload.make_workflows(MIX, 0.3, 20, 1024, 122753, BIG_SEED)
    b = workload.make_workflows(MIX, 0.3, 20, 1024, 122753, BIG_SEED)
    assert [wf.arrival for wf in a] == [wf.arrival for wf in b]
    for x, y in zip(_flat(a), _flat(b)):
        assert np.array_equal(x.prefix, y.prefix)
        assert np.array_equal(x.own, y.own)
        assert (x.plen, x.out) == (y.plen, y.out)


def test_seeds_change_the_tokens_and_not_the_work():
    a = workload.make_workflows(MIX, 0.3, 40, 1024, 122753, 1)
    b = workload.make_workflows(MIX, 0.3, 40, 1024, 122753, BIG_SEED)
    assert [wf.arrival for wf in a] == [wf.arrival for wf in b]
    assert [(s.parents, s.out, s.prompt_len) for s in _flat(a)] == \
        [(s.parents, s.out, s.prompt_len) for s in _flat(b)]
    assert not np.array_equal(_flat(a)[0].own, _flat(b)[0].own)
    n_lead, n_win, _ = workload.counts(MIX, 0.3, 40)
    assert all(0 <= w.arrival < 40 for w in a[n_lead:n_lead + n_win])


@pytest.mark.parametrize("max_len,top", [(1024, 640), (2048, 1664)])
def test_prompts_are_bucketed_and_capped(max_len, top):
    assert workload.prompt_buckets(MIX, max_len)[-1] == top
    assert workload.prompt_buckets(MIX, max_len)[0] == 256
    wfs = workload.make_workflows(MIX, 0.5, 40, max_len, 50280, 7)
    _serve(wfs)
    for s in _flat(wfs):
        assert s.prompt_len % 128 == 0 and 256 <= s.prompt_len <= top
        assert s.prompt.shape == (s.prompt_len,)
        assert s.prompt_len + MIX["out_cap"] <= max_len - 1
        assert MIX["out_min"] <= s.out <= MIX["out_cap"]
        assert s.prompt.dtype == np.int32 and s.prompt.max() < 50280


def test_arrival_segments():
    arr = workload.arrival_times(MIX, 0.4, 30)
    n_lead, n_win, n_tail = workload.counts(MIX, 0.4, 30)
    assert (n_lead, n_win, n_tail) == (6, 12, 36)
    assert np.all(np.diff(arr) >= 0)
    assert np.all(arr[:n_lead] < 0) and arr[0] >= -MIX["lead_s"]
    assert np.all((arr[n_lead:n_lead + n_win] >= 0)
                  & (arr[n_lead:n_lead + n_win] < 30))
    assert np.all(arr[n_lead + n_win:] >= 30)


def test_child_prompt_starts_with_its_parent_and_roots_share_prefixes():
    """A child's prompt is its workflow's system prompt, then the newest
    of what its parents served, then tokens of its own: no step repeats
    its parent's prompt."""
    wfs = workload.make_workflows(MIX, 0.5, 40, 2048, 50280, 3)
    served = _serve(wfs)
    P = MIX["prefix_tokens"]
    seen = 0
    for wf in wfs:
        for s in wf.steps:
            assert np.array_equal(s.prompt[:P], wf.steps[0].prefix)
            k = min(s.own.shape[0], s.prompt_len - P)
            assert k > 0 and np.array_equal(s.prompt[-k:], s.own[-k:])
            if not s.parents:
                continue
            last = served[wf.wid, s.parents[-1]][P:]
            room = s.prompt_len - P - s.own.shape[0]
            if room > 0:    # the last parent's newest tokens come next
                n = min(room, last.shape[0])
                assert np.array_equal(s.prompt[-k - n:-k], last[-n:])
            for p in s.parents:
                assert not np.array_equal(s.prompt, wf.steps[p].prompt)
            seen += 1
    assert seen > 0
    heads = {tuple(wf.steps[0].prefix) for wf in wfs}
    assert len(heads) < len(wfs)       # some workflows share a system prompt


@pytest.mark.parametrize("max_len", [1024, 2048])
def test_every_child_carries_new_tokens(max_len):
    """However many tokens the parents carry, a child keeps at least
    its own input (or all of its room after the system prompt)."""
    wfs = workload.make_workflows(MIX, 0.5, 60, max_len, 50280, 11)
    for wf, shape in zip(wfs, workload.pool(MIX, len(wfs))):
        for s, st in zip(wf.steps, shape.steps):
            room = s.prompt_len - MIX["prefix_tokens"]
            assert s.own.shape[0] == min(max(st[3], room - sum(
                wf.steps[p].plen - MIX["prefix_tokens"] + wf.steps[p].out
                for p in s.parents)), room)
    capped, dropped = workload.clipped_share(MIX, 0.5, 60, max_len)
    assert 0 < capped < 1 and 0 < dropped < 1


def test_deadline_is_slo_scale_times_solo_latency():
    s = workload.Step(0, 0, (), 256, 100, np.zeros(128, np.int32),
                      np.zeros(128, np.int32))
    solo = {"ttft_base_s": 0.1, "ttft_per_token_s": 1e-3, "tpot_s": 0.05}
    assert workload.deadline(s, solo, 3.0) == pytest.approx(
        3.0 * (0.1 + 0.256 + 100 * 0.05))
