"""The harness driven end to end on the CPU at toy widths, past its look
for a chip: the result line's keys, a sound run is ``correct``, the control
(the reference in the nearest lower precision, in the program's place) is
not, and a timed path broken underneath is not."""

import json
import time

import pytest

from benchmark import cells, compare, device, run, serve, train
from conftest import FIXTURES

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _measure(bench, workload, trace=False, **kw):
    line = run.measure(workload, 2_147_483_999, 1.0, trace,
                       device.device_info(), time.perf_counter(),
                       bench=bench, base=FIXTURES, **kw)
    return json.loads(line)


def test_no_chip_means_no_result_line(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet50-1chip", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(RuntimeError, match="not in benchmark.device.PEAKS"):
        device.peaks("TPU v9 imaginary")
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-resnet-one", {"train_rate", "setup_s"}),
    ("tiny-resnet-dp4", {"dp_train_rate", "setup_s"}),
    ("tiny-gpt-train", {"train_rate", "setup_s"}),
    ("tiny-gpt-serve", {"tpot_p50_ms", "tpot_p90_ms", "setup_s"}),
])
def test_sound_run_prints_the_contracts_line(tiny_bench, capsys, workload,
                                             metrics):
    out = _measure(tiny_bench, workload)
    assert set(out) == LINE_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    earlier = capsys.readouterr().out
    assert "benchmark:setup" in earlier and "benchmark:compared" in earlier
    assert ("benchmark:subwindows" in earlier) == any(
        m.endswith("train_rate") for m in metrics)
    compared = [json.loads(l.split(" ", 1)[1]) for l in earlier.splitlines()
                if l.startswith("benchmark:compared")][0]
    for row in compared["compared"]:       # each number beside its limit
        assert set(row) == {"number", "value", "limit", "inside"}


def _broken_build(real):
    """A step that returns its state unchanged: it reports the loss of the
    state it was given and updates nothing."""
    import jax
    import jax.numpy as jnp

    def build(*a):
        prog = real(*a)
        step = prog._step

        def frozen(*args):
            state, batch = args[:-1], args[-1]
            kept = jax.tree_util.tree_map(jnp.copy, state)
            *_new, loss = step(*state, batch)
            return (*kept, loss)

        prog._step = frozen
        return prog

    return build


@pytest.mark.parametrize("workload", ["tiny-resnet-dp4", "tiny-gpt-train"])
def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        tiny_bench, capsys, workload):
    resolved = cells.resolve(tiny_bench, workload, FIXTURES)
    real = cells.resolve_callable(resolved["config"]["builder"])
    out = _measure(tiny_bench, workload, build=_broken_build(real))
    assert out["correct"] is False
    rows = {r["number"]: r for r in [
        json.loads(l.split(" ", 1)[1]) for l in
        capsys.readouterr().out.splitlines()
        if l.startswith("benchmark:compared")][0]["compared"]}
    assert not rows["dparam_norm_gap"]["inside"]
    assert rows["dparam_norm_gap"]["value"] == pytest.approx(1.0, abs=0.05)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_bench, monkeypatch):
    from horovod_tpu.serving import engine as E

    real = E.InferenceEngine._sample

    def altered(self, req, logits):
        tok = real(self, req, logits)
        return (tok + 1) % self.cfg.vocab_size if len(req.generated) == 2 \
            else tok

    monkeypatch.setattr(E.InferenceEngine, "_sample", altered)
    out = _measure(tiny_bench, "tiny-gpt-serve")
    assert out["correct"] is False


@pytest.mark.parametrize("workload,chips", [("tiny-resnet-dp4", 4),
                                            ("tiny-gpt-train", 4)])
def test_training_control_in_lower_precision_is_not_correct(
        tiny_bench, workload, chips):
    """The control: the reference computed in fp8, the nearest precision
    below the configuration's bf16, put in the program's place."""
    r = cells.resolve(tiny_bench, workload, FIXTURES)
    cfg, job, ref = r["config"], r["traffic"], r["ref"]
    model, seed = cfg["model"], 5
    rows = job["per_chip_batch"] * chips
    batch = ref.make_batch(model, job, seed, rows)
    p0 = ref.init_params(model, seed)
    want = ref.train_reference(model, job, p0, batch, 3, chips, "f32")
    limits = cfg["check"]["limits"]

    def judged(mode):
        got = ref.train_reference(model, job, p0, batch, 3, chips, mode)
        nums = train.numbers_compared(got, want)["numbers"]
        return compare.verdict(nums, {k: limits[k] for k in nums})

    assert judged(cfg["control_precision"])["correct"] is False
    assert judged("f32")["correct"] is True


def test_serving_control_in_lower_precision_is_not_correct(tiny_bench):
    from benchmark import loadgen

    r = cells.resolve(tiny_bench, "tiny-gpt-serve", FIXTURES)
    cfg, traffic, ref = r["config"], r["traffic"], r["ref"]
    model = cfg["model"]
    params = ref.init_params(model, 5)
    planned = loadgen.plan(traffic, 1.0, 5, model["vocab_size"])
    # Greedy tokens of the float32 reference itself stand in for a sound
    # server here; the control's first choices are judged against them.
    results = []
    for p in planned:
        seq = list(p.prompt)
        for _ in range(p.max_tokens):
            seq.append(int(ref.served_logits(model, params, [seq])[0][-1]
                           .argmax()))
        results.append(loadgen.Done(ok=True, tokens=seq[len(p.prompt):]))
    sample = serve.check_sample(planned, results, 5, 4)
    assert len(planned[sample[0]].prompt) + len(results[sample[0]].tokens) \
        == max(len(p.prompt) + len(d.tokens)
               for p, d in zip(planned, results))
    limit = cfg["check"]["limits"]["served_gap_max"]
    sound = serve.served_gap(ref, model, params, planned, results, sample)
    assert sound["served_gap_max"] <= limit
    control = serve.served_gap(ref, model, params, planned, results, sample,
                               cfg["control_precision"], control=True)
    assert control["served_gap_max"] > limit


def test_a_new_kind_of_traffic_is_a_runner_named_in_its_file(
        tiny_bench, tmp_path, monkeypatch):
    """No table of kinds: a traffic file names what drives it, so a later
    kind (eager collectives, one process a chip) is a new module and a new
    traffic file, and no edit to ``run.py``."""
    (tmp_path / "zz_runner.py").write_text(
        "def run_cell(resolved, seed, seconds, trace, run, t_start):\n"
        "    return {'correct': True, 'attempted': seed, 'failed': 0,\n"
        "            'end_to_end': {'setup_s': 1.5, 'train_rate': 2.0},\n"
        "            'memory_peak_bytes': 7}\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "zz-eager.json").write_text(
        json.dumps({"runner": "zz_runner:run_cell"}))
    (tmp_path / "configs").mkdir()
    cfg = cells._load_json("configs", "tiny-resnet", FIXTURES)
    (tmp_path / "configs" / "tiny-resnet.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(tiny_bench))
    bench["workloads"].append({"name": "zz-cell", "config": "tiny-resnet",
                               "traffic": "zz-eager", "chips": 1,
                               "why": "fixture"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_rate":
            m["workloads"].append("zz-cell")
    line = run.measure("zz-cell", 41, 1.0, False, device.device_info(),
                       time.perf_counter(), bench=bench, base=str(tmp_path))
    out = json.loads(line)
    assert out["attempted"] == 41 and out["correct"] is True
    assert out["metrics"]["train_rate"]["value"] == 2.0
    assert out["device"]["memory_peak_bytes"] == 7
