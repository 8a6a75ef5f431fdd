"""Device self time by named scope (``scopes.py``): the name-stack rule,
the protobuf reader of the device planes' event metadata, the lookup of
an operation's scope by the program it ran in, and ``optimizer_ms`` on
traces recorded on the chip (the committed trace of PR 12, which
predates the scopes, and ``data/scoped/``, recorded since)."""
import gzip
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmarks.chip import cells, scopes, trace  # noqa: E402

#: the readings of each recorded trace when it was committed: steps,
#: optimizer_ms, device self time by scope summed over the chips (ns),
#: and the programs that ran. scoped/: qwen1.5-4b.train at faulty_run.py's
#: small size (record_trace.py), and the overlapped step on one chip at
#: the reduced smollm-360m config with telemetry on, 3 steps.
RECORDED = {
    "qwen1.5-4b_small.xplane.pb.gz": {
        "steps": 2, "optimizer_ms": None, "scopes": {},
        "programs": {"_lambda"}},
    "scoped/qwen1.5-4b_small.xplane.pb.gz": {
        "steps": 2, "optimizer_ms": 0.021897999999999997,
        "scopes": {"embed": 34106.0, "attn": 107347.0, "layers": 51147.0,
                   "mlp": 86536.0, "head": 27830.0, "xent": 30654.0,
                   "adamw": 43796.0},
        "programs": {"_lambda"}},
    "scoped/smollm-360m_overlapped_small.xplane.pb.gz": {
        "steps": 3, "optimizer_ms": 0.010498,
        "scopes": {"embed": 57086.0, "attn": 432699.0, "mlp": 96181.0,
                   "xent": 85623.0, "head": 40114.0, "adamw": 31494.0},
        "programs": {"fwd", "head_bwd", "chunk_bwd", "embed_bwd", "apply",
                     "allreduce.pip_mcoll"}},
}


def ev(name, s, e):
    return NS(name=name, start_ns=float(s), end_ns=float(e))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs)
                                for n, evs in lines.items()])


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(<lambda>)/adamw/mul:", "adamw"),
    ("jit(step)/transpose(jvp(xent))/mul;jit(step)/transpose(jvp(xent))"
     "/broadcast_in_dim", "xent"),
    ("jit(<lambda>)/jvp(layers)/while/body/closed_call/attn/dot_general:",
     "attn"),
    ("jit(<lambda>)/transpose(jvp(layers))/while/body/dynamic_update_slice:",
     "layers"),
    ("jit(<lambda>)/transpose(jvp())/while/body/closed_call/dot_general:",
     None),
    ("jit(fwd)/jit(silu)/mul:", None),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _pb(*fields):
    """A protobuf message from (field, value) pairs: an int is a varint,
    bytes or a str length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_scopes_reads_the_device_planes_event_metadata():
    """Two programs (ids 11 and 22) hold an operation of the same name;
    its tf_op is a string in one and a reference to a stat metadata's
    name in the other. Host planes and unscoped operations give
    nothing."""
    stat_meta = lambda i, name: (5, _pb((1, i), (2, _pb((1, i), (2, name)))))
    op = "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)"

    def event_meta(i, name, pid, tf_op=None, ref=None):
        stats = [(5, _pb((1, 8), (3, pid)))]
        if tf_op is not None:
            stats.append((5, _pb((1, 7), (5, tf_op))))
        if ref is not None:
            stats.append((5, _pb((1, 7), (7, ref))))
        return (4, _pb((1, i), (2, _pb((1, i), (2, name), *stats))))

    device = _pb((1, 3), (2, "/device:TPU:0"), stat_meta(7, "tf_op"),
                 stat_meta(8, "program_id"),
                 stat_meta(9, "jit(apply)/adamw/sub:"),
                 event_meta(1, op, 11, tf_op="jit(fwd)/jvp(embed)/mul:"),
                 event_meta(2, op, 22, ref=9),
                 event_meta(3, "%copy.2", 22, tf_op="jit(apply)/copy:"))
    host = _pb((1, 4), (2, "/host:CPU"), stat_meta(7, "tf_op"),
               event_meta(1, "dispatch", 0, tf_op="adamw/x"))
    raw = _pb((1, host), (1, device), (2, "an error string"))
    assert scopes.op_scopes(raw) == {(11, op): "embed", (22, op): "adamw"}


def _profile():
    """One chip, a window [0, 100): fusion.1 runs in program fwd (11) at
    0-10 and in program apply (22) at 60-70, and apply's copy.2 at
    70-80, partly past the window's end at 95-105."""
    host = plane("/host:CPU", {"python": [ev("window", 0, 100),
                                          ev("dispatch", 0, 60)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 0, 10), ev("fusion.1", 60, 70),
                    ev("copy.2", 70, 80), ev("fusion.1", 95, 105)],
        "XLA Modules": [ev("jit_fwd(11)", 0, 10),
                        ev("jit_apply(22)", 60, 80),
                        ev("jit_apply(22)", 95, 105)]})
    return NS(planes=[host, dev])


def test_an_operations_scope_is_looked_up_by_its_program():
    """fusion.1 is adamw's in program 22 and has no scope in program
    11: its name alone does not say which."""
    got = scopes.self_times(_profile(), {(22, "fusion.1"): "adamw"},
                            (0.0, 100.0))
    assert got == {0: {("fwd", None): 10.0, ("apply", "adamw"): 15.0,
                       ("apply", None): 10.0}}


def test_optimizer_ms_by_hand(monkeypatch):
    s = trace.summarize(_profile())
    ctx = NS(summary=s, steps=1, xplane="unused")
    monkeypatch.setattr(scopes, "load", lambda path: (
        _profile(), {(22, "fusion.1"): "adamw"}))
    assert cells.load_reader("optimizer_ms").read(ctx) == pytest.approx(
        15e-6)
    monkeypatch.setattr(scopes, "load", lambda path: (_profile(), {}))
    assert cells.load_reader("optimizer_ms").read(ctx) is None
    assert cells.load_reader("optimizer_ms").read(
        NS(summary=None, steps=1, xplane="unused")) is None


@pytest.fixture(scope="module", params=sorted(
    [p.relative_to(HERE / "data").as_posix()
     for p in (HERE / "data").glob("*.xplane.pb.gz")]
    + [p.relative_to(HERE / "data").as_posix()
       for p in (HERE / "data" / "scoped").glob("*.xplane.pb.gz")]))
def recorded(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (HERE / "data" / request.param).read_bytes()))
    return request.param, str(path)


def test_scope_readings_as_committed(recorded):
    name, path = recorded
    want = RECORDED[name]
    profile, found = scopes.load(path)
    s = trace.summarize(profile)
    assert s.steps == want["steps"]
    got, programs = {}, set()
    for by in scopes.self_times(profile, found, s.window).values():
        for (prog, scope), t in by.items():
            programs.add(prog)
            if scope is not None:
                got[scope] = got.get(scope, 0.0) + t
    assert got == pytest.approx(want["scopes"], rel=1e-9)
    assert programs == want["programs"]
    ctx = NS(summary=s, steps=s.steps, xplane=path)
    opt = cells.load_reader("optimizer_ms").read(ctx)
    if want["optimizer_ms"] is None:
        assert opt is None
    else:
        assert opt == pytest.approx(want["optimizer_ms"], rel=1e-9)
