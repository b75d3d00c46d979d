"""The schedule is the traffic file's, never the seed's."""
import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.harness import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bytes(requests):
    return json.dumps([(r.index, r.due_s, r.prompt_len, r.max_tokens,
                        r.client) for r in requests]).encode()


def test_chat_schedule_is_identical_under_any_seed():
    mix = traffic.load_traffic("serve.chat")
    a, b = traffic.schedule(mix), traffic.schedule(mix)
    assert _bytes(a) == _bytes(b)
    # --seed reaches only the token ids: lengths stay the schedule's
    pa = traffic.prompt_tokens(a, 92544, 7)
    pb = traffic.prompt_tokens(a, 92544, 2**31 + 12345)
    assert [len(x) for x in pa] == [len(x) for x in pb] \
        == [r.prompt_len for r in a]
    assert any((x != y).any() for x, y in zip(pa, pb))
    assert all(0 <= x.min() and x.max() < 92544 for x in pa)


def test_chat_schedule_in_another_process_is_byte_identical():
    code = ("import sys, json; sys.path.insert(0, %r);"
            "from benchmarks.harness import traffic as t;"
            "print(json.dumps([(r.index, r.due_s, r.prompt_len, r.max_tokens,"
            " r.client) for r in t.schedule(t.load_traffic('serve.chat'))]))"
            % ROOT)
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True).stdout.strip()
    assert out == _bytes(traffic.schedule(traffic.load_traffic("serve.chat")))


def test_chat_totals_in_the_51_second_window():
    mix = traffic.load_traffic("serve.chat")
    reqs = traffic.schedule(mix)
    tot = traffic.totals(reqs, 51.0)
    # hand-checked once against the generator's own output for this file
    assert tot["requests"] == sum(1 for r in reqs if r.due_s < 51.0)
    assert tot["requests"] == 396        # 8.4 requests/s for 51 s
    lens = np.array([r.prompt_len for r in reqs])
    outs = np.array([r.max_tokens for r in reqs])
    assert lens.min() >= 32 and lens.max() <= 2048
    assert outs.min() >= 16 and outs.max() <= 256
    assert 200 <= np.median(lens) <= 320 and 50 <= np.median(outs) <= 80
    assert all(b.due_s > a.due_s for a, b in zip(reqs, reqs[1:]))
    assert max(r.prompt_len + r.max_tokens for r in reqs) <= 4096


def test_a_sweep_stretches_the_same_schedule():
    mix = traffic.load_traffic("serve.chat")
    slow = traffic.schedule(dict(mix, rate_rps=1.0))
    fast = traffic.schedule(dict(mix, rate_rps=4.0))
    assert [r.prompt_len for r in slow] == [r.prompt_len for r in fast]
    assert np.allclose([r.due_s for r in slow],
                       [4.0 * r.due_s for r in fast])


def test_batch_clients_have_fixed_lists():
    mix = traffic.load_traffic("serve.batch")
    reqs = traffic.schedule(mix)
    assert len(reqs) == mix["clients"] * mix["requests_per_client"]
    assert {r.client for r in reqs} == set(range(16))
    assert all(r.max_tokens == 128 for r in reqs)
    assert all(256 <= r.prompt_len <= 1024 for r in reqs)
    assert _bytes(reqs) == _bytes(traffic.schedule(mix))


def test_every_cell_finds_its_files_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["name"] == cell["config"] + "." + cell["traffic"]
        assert os.path.exists(os.path.join(ROOT,
                                           configs[cell["config"]]["file"]))
        traffic.load_traffic(cell["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".py")), m["name"]
