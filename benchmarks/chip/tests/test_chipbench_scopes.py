"""Device self time by the program's named scopes: on a hand-made
nested trace with known answers, on a hand-written xplane, and on a
small trace of one training step recorded on a TPU v5e."""
from __future__ import annotations

import importlib
import os

import pytest

from chip import harness, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "booking_train_trace.json.gz")
# one training step of booking-train, with each op's op_name path
SCOPED = os.path.join(HERE, "data", "booking_train_scoped_trace.json.gz")
SCOPE_METRICS = ["train_item_logits_fwd_device_pct",
                 "train_item_logits_bwd_device_pct",
                 "train_loss_head_device_pct", "train_encoder_device_pct",
                 "train_optimizer_device_pct", "train_unscoped_device_pct"]

# two devices, a 100 ns window; op_name paths as JAX writes them
# device 0: the logits' forward while 10-50 with its body 12-30 and
#   32-45 (self 9), an op under the encoder and the logits scopes 80-85
#   (the logits, innermost), the backward while 55-70 with its body
#   56-69, an unscoped copy 72-80, a loss-head op from -5 (5 in the
#   window), an optimizer op to 110 (10 in the window)
# device 1: the encoder's backward 0-40, the logits' forward while
#   50-100 with its body 60-80
FWD = "jit(train_step)/jvp(item_emb.logits)/while"
BWD = "jit(train_step)/transpose(jvp(item_emb.logits))/while"
NESTED = {
    "devices": {
        "/device:TPU:0": [
            ["while.1", 10, 40, FWD],
            ["fusion.2", 12, 18, FWD + "/body/add"],
            ["fusion.3", 32, 13, FWD + "/body/add"],
            ["dot.9", 80, 5,
             "jit(train_step)/jvp(seqrec.encode)/item_emb.logits/dot"],
            ["while.4", 55, 15, BWD],
            ["scatter.5", 56, 13, BWD + "/body/scatter-add"],
            ["copy.6", 72, 8, "jit(train_step)/copy"],
            ["reduce.7", -5, 10,
             "jit(train_step)/jvp(seqrec.loss_head)/reduce"],
            ["sub.8", 90, 20, "jit(train_step)/train.optimizer/sub"]],
        "/device:TPU:1": [
            ["dot.1", 0, 40,
             "jit(train_step)/transpose(jvp(seqrec.encode))/dot_general"],
            ["while.2", 50, 50, FWD], ["fusion.3", 60, 20, FWD + "/body/add"]],
    },
    "spans": [["bench.window", 0, 100]],
}


def test_self_time_of_nested_events_by_hand():
    ops = [(n, s, s + d)
           for n, s, d, _ in NESTED["devices"]["/device:TPU:0"]]
    own = dict(zip([o[0] for o in ops], scopes.self_ns(ops, (0, 100))))
    # the while less its body; an op crossing an edge counts inside it
    assert own == {"while.1": 9, "fusion.2": 18, "fusion.3": 13, "dot.9": 5,
                   "while.4": 2, "scatter.5": 13, "copy.6": 8,
                   "reduce.7": 5, "sub.8": 10}


@pytest.mark.parametrize("scope, backward, ns", [
    ("item_emb.logits", False, (45 + 50) / 2),
    ("item_emb.logits", True, 15 / 2),
    ("item_emb.logits", None, (60 + 50) / 2),
    ("seqrec.encode", None, 40 / 2),
    ("seqrec.encode", False, 0.0),
    ("seqrec.loss_head", None, 5 / 2),
    ("train.optimizer", None, 10 / 2),
    (None, None, 8 / 2),
])
def test_scope_self_seconds_by_hand(scope, backward, ns):
    s = scopes.from_events(NESTED)
    assert s.self_seconds(scope, backward) * 1e9 == pytest.approx(ns)


def test_scope_shares_and_idle_make_the_window():
    s = scopes.from_events(NESTED)
    shares = [s.pct(x) for x in scopes.SCOPES] + [s.pct(None)]
    r = s.reduced
    assert sum(shares) + 100.0 * r.idle_share() == pytest.approx(100.0)
    assert r.idle_share() == pytest.approx(0.135)


def test_a_trace_without_scopes_leaves_the_shares_unread():
    assert scopes.from_events(trace.load(RECORDED)).pct(None) is None
    bare = {"devices": {"/device:TPU:0": [
        ["fusion.1", 0, 10, "jit(f)/add"]]}, "spans": []}
    assert not scopes.from_events(bare).scoped()
    assert scopes.from_events(bare).pct(None) is None


@pytest.mark.parametrize("path, layer", [
    ("jit(train_step)/jvp(seqrec.encode)/dot_general", "seqrec.encode"),
    ("jit(train_step)/transpose(jvp(item_emb.logits))/while/body/add",
     "item_emb.logits"),
    ("jit(train_step)/jvp(seqrec.encode)/item_emb.logits/dot",
     "item_emb.logits"),
    ("jit(train_step)/train.optimizer/sqrt", "train.optimizer"),
    ("jit(train_step)/copy", None),
    ("", None),
])
def test_layer_is_the_innermost_scope(path, layer):
    assert scopes.layer_of(path) == layer


def _run(ev=None) -> harness.Run:
    run = harness.Run(name="booking-train", config={}, traffic={},
                      chips=1, peaks=harness.peaks("TPU v5 lite"),
                      seconds=10.0)
    if ev is not None:
        s = scopes.from_events(ev)
        run.trace, run.scoped = s.reduced, s
    return run


def _read(name: str, path: str):
    run = _run(trace.load(path))
    return importlib.import_module(f"chip.metrics.{name}").read(run)


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_reads_the_recorded_step(name):
    assert 0.0 <= _read(name, SCOPED) <= 100.0
    # recorded before the program named its scopes: nothing to read
    assert _read(name, RECORDED) is None


def test_recorded_step_splits_by_scope():
    pct = {n: _read(n, SCOPED) for n in SCOPE_METRICS}
    idle = _read("train_device_idle_pct", SCOPED)
    assert sum(pct.values()) + idle == pytest.approx(100.0)
    # the JPQ logits' gather loop forward, their scatter-add backward
    assert pct["train_item_logits_fwd_device_pct"] > 50.0
    assert pct["train_item_logits_bwd_device_pct"] > 15.0
    assert pct["train_unscoped_device_pct"] < 5.0
    assert 0.0 < pct["train_optimizer_device_pct"] < 1.0


# an xplane as the TPU profiler writes one: the op_name path is the
# ``tf_op`` stat of each op's event metadata, a string or a reference to
# a stat metadata named by it; an op with no path (a layout copy)
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 6000 duration_ps: 2000
             stats { metadata_id: 3 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 9000 duration_ps: 1000 }
    events { metadata_id: 1 offset_ps: 11000 duration_ps: 1000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 4 offset_ps: 0 duration_ps: 12000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 1
            str_value: "jit(train_step)/jvp(seqrec.encode)/dot_general:" }
    stats { metadata_id: 2 str_value: "loop fusion" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = f32[8] while()"
    stats { metadata_id: 2 str_value: "while" }
    stats { metadata_id: 1 ref_value: 5 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy()" } }
  event_metadata { key: 4 value { id: 4 name: "jit_train_step(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  stat_metadata { key: 3 value { id: 3 name: "run_id" } }
  stat_metadata { key: 5 value { id: 5
    name: "jit(train_step)/transpose(jvp(item_emb.logits))/while:" } }
}
planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" } }
"""


def _xplane(root, text: str = XSPACE) -> str:
    from jax.profiler import ProfileData
    d = root / scopes.TRACE_DIR / "booking-train" / "plugins"
    d.mkdir(parents=True)
    path = d / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_op_paths_read_each_ops_path_from_its_metadata(tmp_path):
    path = _xplane(tmp_path)
    assert scopes.op_paths(path) == {"/device:TPU:0": [
        ("%fusion.1 = f32[8] fusion()",
         "jit(train_step)/jvp(seqrec.encode)/dot_general"),
        ("%while.2 = f32[8] while()",
         "jit(train_step)/transpose(jvp(item_emb.logits))/while"),
        ("%copy.3 = f32[8] copy()", ""),
        ("%fusion.1 = f32[8] fusion()",
         "jit(train_step)/jvp(seqrec.encode)/dot_general")]}


def test_a_traced_run_reads_its_scopes_from_its_xplane(tmp_path):
    path = _xplane(tmp_path)
    run = _run()
    run.trace = trace.Reduced(trace.events(path))
    assert scopes.trace_file("booking-train", str(tmp_path)) == path
    s = scopes.of_run(run, str(tmp_path))
    assert s.self_seconds("item_emb.logits", True) == pytest.approx(2e-9)
    assert s.self_seconds(None) == pytest.approx(1e-9)
    assert s.self_seconds("seqrec.encode", False) == pytest.approx(6e-9)
    # read once: the run keeps it
    assert scopes.of_run(run, str(tmp_path)) is s


def test_an_xplane_of_another_run_is_not_read(tmp_path):
    _xplane(tmp_path)
    run = _run()
    run.trace = trace.Reduced({"devices": {"/device:TPU:0": [
        ["%fusion.1 = f32[8] fusion()", 0, 5]]}, "spans": []})
    assert scopes.of_run(run, str(tmp_path)) is None
    # nor is a run without a trace, or one whose xplane is missing
    assert scopes.of_run(_run(), str(tmp_path)) is None
    other = _run()
    other.trace = run.trace
    assert scopes.of_run(other, str(tmp_path / "none")) is None
