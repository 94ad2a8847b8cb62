"""The benchmark's traffic and window loops, on the CPU at toy size.

The generators must be deterministic per seed and differ across seeds;
the serving window's bookkeeping (open-loop arrivals, admission, drain,
wave-end token times) must give the right attempted, failed, TTFT and
TPOT; the harness must refuse a machine without a TPU.
"""
import collections
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import core, fl_data

ROOT = pathlib.Path(__file__).resolve().parents[2]
serve = core.driver("serve_open_loop")
fl = core.driver("fl_rounds")

PROMPT_HEAVY = core.traffic("serve-prompt-heavy")
DECODE_HEAVY = core.traffic("serve-decode-heavy")


@pytest.mark.parametrize("t", [PROMPT_HEAVY, DECODE_HEAVY],
                         ids=["prompt-heavy", "decode-heavy"])
def test_schedule_is_a_function_of_the_seed(t):
    a1, p1 = serve.schedule(t, 40.0, 2 ** 40 + 3, 50304)
    a2, p2 = serve.schedule(t, 40.0, 2 ** 40 + 3, 50304)
    a3, p3 = serve.schedule(t, 40.0, 7, 50304)
    assert np.array_equal(a1, a2)
    assert all(np.array_equal(x, y) for x, y in zip(p1, p2))
    assert not np.array_equal(a1, a3)
    assert any(len(x) != len(y) or not np.array_equal(x, y)
               for x, y in zip(p1, p3))
    # the same amount of work in another order
    assert sorted(map(len, p1)) == sorted(map(len, p3))
    assert np.isclose(a1[-1], a3[-1])


@pytest.mark.parametrize("t", [PROMPT_HEAVY, DECODE_HEAVY],
                         ids=["prompt-heavy", "decode-heavy"])
def test_schedule_lengths_rate_and_clips(t):
    arrivals, prompts = serve.schedule(t, 200.0, 11, 50304)
    lengths = np.array([len(p) for p in prompts])
    p = t["prompt"]
    assert lengths.min() >= p["min"] and lengths.max() <= p["max"]
    assert abs(np.median(lengths) - p["median"]) <= 0.02 * p["median"] + 1
    # lognormal spread: log-lengths of the unclipped middle half
    logs = np.log(np.sort(lengths)[len(lengths) // 4: 3 * len(lengths) // 4])
    assert np.std(logs) < p["sigma"]
    gaps = np.diff(np.concatenate([[0.0], arrivals]))
    assert abs(np.mean(gaps) * t["rate_per_s"] - 1) < 0.05
    assert all(p_.dtype == np.int32 and p_.max() < 50304 for p_ in prompts)


def test_token_federation_is_the_papers_protocol():
    t = core.traffic("fl-masked")
    a = fl_data.token_federation(t, 50304, np.random.default_rng(5))
    b = fl_data.token_federation(t, 50304, np.random.default_rng(5))
    c = fl_data.token_federation(t, 50304, np.random.default_rng(6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["client_x"], c["client_x"])
    assert a["client_x"].shape == (8, 14, 512)
    assert a["server_x"].shape == (6, 512)
    assert a["test_x"].shape == (8, 512)
    assert np.array_equal(a["client_x"][:, :, 1:], a["client_y"][:, :, :-1])
    # label-shard skew: every client holds at most 2 topics' worth of shards
    assert (np.count_nonzero(a["client_dists"], axis=1) <= 4).all()
    hp = fl.hyper({}, t, a)
    assert (hp["local_steps"], hp["tau"]) == (7, 3)


def test_image_federation_is_the_papers_protocol():
    t = core.traffic("fl-paper")
    t = dict(t, data=dict(t["data"], train=2500, test=200, device_pool=2000),
             clients=20)
    dm = {"image": (8, 8, 3), "classes": 10}
    a = fl_data.federation(t, dm, np.random.default_rng(5))
    b = fl_data.federation(t, dm, np.random.default_rng(5))
    c = fl_data.federation(t, dm, np.random.default_rng(6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["client_x"], c["client_x"])
    assert a["client_x"].shape == (20, 100, 8, 8, 3)
    assert a["client_x"].dtype == np.float32
    # p = 5% of the device pool, drawn from the images outside it
    assert a["server_x"].shape == (100, 8, 8, 3)
    assert a["test_x"].shape == (200, 8, 8, 3)
    # two label shards each: at most 2 labels, or 3 where a shard straddles
    assert (np.count_nonzero(a["client_dists"], axis=1) <= 4).all()
    assert np.allclose(a["client_dists"].sum(1), 1)
    full = core.traffic("fl-paper")
    hp = fl.hyper({}, full, {"client_x": np.zeros((100, 400, 1)),
                             "server_x": np.zeros((2000, 1)),
                             "test_x": np.zeros((10000, 1))})
    assert (hp["local_steps"], hp["tau"]) == (200, 62)


class FakeEngine:
    """The decode engine's host protocol, with a fixed wave time: FIFO
    admission into free slots at the start of a wave, one prompt token a
    step, then ``max_new_tokens`` generated tokens, retired at the end of
    the wave that produced the last one."""

    def __init__(self, cfg, wave_s):
        self.cfg, self.wave_s = cfg, wave_s
        self.queue = collections.deque()
        self.slots = [None] * cfg.slots
        self.uid = 0

    @property
    def pending(self):
        return len(self.queue) + sum(s is not None for s in self.slots)

    def submit(self, prompt):
        self.queue.append((self.uid, len(prompt)))
        self.uid += 1
        return self.uid - 1

    def step_wave(self):
        from repro.serving import Completion

        for i, s in enumerate(self.slots):
            if s is None and self.queue:
                uid, n = self.queue.popleft()
                self.slots[i] = [uid, n, 0]
        time.sleep(self.wave_s)
        done = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            s[2] += self.cfg.steps_per_wave
            if s[2] >= s[1] - 1 + self.cfg.max_new_tokens:
                done.append(Completion(s[0], np.zeros(s[1], np.int32),
                                       np.zeros(self.cfg.max_new_tokens,
                                                np.int32)))
                self.slots[i] = None
        return done


def test_open_loop_window_times_every_request():
    from repro.serving import ServeConfig

    cfg = ServeConfig(slots=2, cache_len=64, max_prompt=16,
                      max_new_tokens=8, steps_per_wave=4)
    engine = FakeEngine(cfg, wave_s=0.02)
    arrivals = np.array([0.0, 0.0, 0.0, 0.3, 5.0])   # the last never arrives
    prompts = [np.zeros(n, np.int32) for n in (4, 4, 9, 1, 4)]
    loop = serve.run_window(engine, arrivals, prompts, seconds=0.5,
                            drain_limit=5.0)
    out = serve.summarize(loop, cfg)
    assert out["attempted"] == 4 and out["failed"] == 0
    assert loop.mismatched == 0
    assert [r.admit_wave for r in loop.requests][:3] == [0, 0, 3]
    # request 0: prompt 4, first token in wave 0, last (8th) in wave 2
    r0 = loop.requests[0]
    assert loop.first_wave(r0, 4) == 0 and loop.token_wave(r0, 7, 4) == 2
    # request 2 waits for a slot: admitted at wave 3, first token in wave 5
    r2 = loop.requests[2]
    assert loop.first_wave(r2, 4) == 5
    ttft = sorted((loop.wave_ends[loop.first_wave(r, 4)] - r.arrival) * 1e3
                  for r in loop.requests)
    assert out["e2e"]["ttft_p95_ms"] == pytest.approx(core.p95(ttft))
    assert 20 * 5 <= ttft[-1] < 20 * 5 + 200
    assert out["e2e"]["tpot_p95_ms"] == pytest.approx(
        max((loop.wave_ends[loop.token_wave(r, 7, 4)]
             - loop.wave_ends[loop.first_wave(r, 4)]) / 7 * 1e3
            for r in loop.requests), rel=0.2)
    assert out["notes"]["drain_s"] < 1.0


def test_open_loop_counts_rejections_and_lost_requests():
    from repro.serving import ServeConfig

    cfg = ServeConfig(slots=1, cache_len=64, max_prompt=16,
                      max_new_tokens=8, steps_per_wave=4)

    class Full(FakeEngine):
        def submit(self, prompt):          # room for one request only
            return None if self.uid >= 1 else super().submit(prompt)

    engine = Full(cfg, wave_s=0.01)
    loop = serve.run_window(engine, np.array([0.0, 0.0, 0.0]),
                            [np.zeros(4, np.int32)] * 3, seconds=0.2,
                            drain_limit=2.0)
    out = serve.summarize(loop, cfg)
    assert out["attempted"] == 3 and out["failed"] == 2


def test_chunk_loop_counts_failed_rounds():
    assert fl.count_failed(1.0, np.zeros(5)) == 0
    assert fl.count_failed(1.0, np.array([0, 1, 0, 2, 0.0])) == 2
    assert fl.count_failed(float("nan"), np.zeros(5)) == 5


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(ROOT / "src")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "olmo-1b-l4.fl-masked", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_names_files_that_exist():
    bench = core.benchmark()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        core.config(w["config"])
        assert core.traffic(w["traffic"])["driver"] in (
            "fl_rounds", "serve_open_loop")
        assert (core.BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in bench["per_layer"]:
        assert callable(core.metric_reader(m["name"]))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert json.loads((ROOT / "BENCHMARK.json").read_text()) == bench
