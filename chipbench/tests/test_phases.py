"""phases.py: scope attribution, gap naming and unspanned idle on
synthetic events, and the tf_op decoding on a recorded v5e trace."""
from pathlib import Path

from chipbench import phases as ph
from chipbench import trace_reduce as tr

from test_trace_reduce import COPY, DOT, KERNEL, LOOP, WHILE

D = "/device:TPU:0"
PROG = 7
# a v5e trace of a jitted two-round lax.scan whose body runs a matmul under
# sfl.client_forward, a three-step scan of matmuls under sfl.server_tau and
# gaussian noise under sfl.replay; the host spans are chipbench.window >
# engine.chunk
RECORDED = Path(__file__).parent / "data" / "v5e_scopes.xplane.pb"


def test_scope_of_takes_the_innermost_of_the_five():
    assert ph.scope_of("jit(run_chunk)/while/body/closed_call/"
                       "sfl.server_tau/while/body/dot_general:") \
        == "sfl.server_tau"
    assert ph.scope_of("jit(f)/vmap(sfl.client_forward)/jit(_normal)/"
                       "iota") == "sfl.client_forward"
    assert ph.scope_of("jit(f)/sfl.replay/sfl.server_eval/add") \
        == "sfl.server_eval"
    assert ph.scope_of("jit(f)/while/body/reduce_sum:") == ph.UNSCOPED
    assert ph.scope_of("jit(f)/sfl.replay_all/mul") == ph.UNSCOPED
    assert ph.scope_of("") == ph.UNSCOPED


def _events():
    host = [tr.Ev("chipbench.window", 1000, 3000),
            tr.Ev("engine.prepare", 1000, 1100),
            tr.Ev("engine.chunk", 1100, 2600),
            tr.Ev("engine.stage", 1100, 1200),
            tr.Ev("engine.dispatch", 1200, 1250),
            tr.Ev("engine.flush", 1250, 2600),
            tr.Ev("np.asarray(jax.Array)", 2300, 2600),
            tr.Ev("engine.finish", 2600, 2700),
            tr.Ev("TpuClient::DefragmentMemory", 2700, 2950)]
    ops = [ph.Op(WHILE, 1100, 2200, D, PROG),      # container: left out
           ph.Op(DOT, 1050, 1300, D, PROG),        # clipped to [1050, 1300)
           ph.Op(LOOP, 1300, 1500, D, PROG),
           ph.Op(KERNEL, 1500, 1600, D, PROG),
           ph.Op(COPY, 1600, 1700, D, PROG),       # no tf_op: unscoped
           ph.Op(LOOP, 1700, 2200, D, PROG + 1),   # another program's op
           ph.Op(DOT, 2500, 2550, D, PROG),
           ph.Op(DOT, 3100, 3200, D, PROG)]        # after the window
    names = {(PROG, DOT): "jit(run_chunk)/while/body/vmap(sfl.client_"
                          "forward)/dot_general:",
             (PROG, LOOP): "jit(run_chunk)/while/body/sfl.server_tau/"
                           "while/body/sfl.replay/add:",
             (PROG, KERNEL): "jit(run_chunk)/while/body/sfl.replay/"
                             "pallas_call:",
             (PROG + 1, LOOP): "jit(_threefry_fold_in)/threefry2x32:"}
    return ops, host, names


def test_phases_add_up_to_the_ops_trace_reduce_counts():
    ops, host, names = _events()
    r = ph.reduce_events(ops, host, names, "chipbench.window")
    s = r["phase_s"]
    assert s["sfl.client_forward"] == 250e-9 + 50e-9
    assert s["sfl.replay"] == 200e-9 + 100e-9
    assert s[ph.UNSCOPED] == 100e-9 + 500e-9
    assert s["sfl.server_tau"] == s["sfl.server_eval"] \
        == s["sfl.zo_backprop"] == 0.0
    red = tr.reduce_events([tr.Ev(o.name, o.start, o.end, o.device)
                            for o in ops], host, "chipbench.window")
    assert abs(sum(s.values()) - (red["matmul_s"] + red["other_s"])) < 1e-18


def test_gaps_named_span_and_runtime_event_and_unspanned_idle():
    ops, host, names = _events()
    r = ph.reduce_events(ops, host, names, "chipbench.window")
    # gaps [1000,1050) [2200,2500) [2550,3000)
    assert r["top_gaps"] == [
        ["no host span/TpuClient::DefragmentMemory", 450e-9],
        ["engine.flush/np.asarray(jax.Array)", 300e-9],
        ["engine.prepare", 50e-9]]
    # [2700, 3000) of the last gap lies outside every program span
    assert r["unspanned_idle_s"] == 300e-9


def test_decodes_op_names_from_a_recorded_v5e_trace():
    names = ph.tf_ops(str(RECORDED))
    scopes = {ph.scope_of(v) for v in names.values()}
    assert {"sfl.client_forward", "sfl.server_tau", "sfl.replay",
            ph.UNSCOPED} <= scopes
    ops, host = ph.load(str(RECORDED))
    assert {o.program for o in ops} == {p for p, _ in names}
    assert any(h.name == "engine.chunk" for h in host)
    # the device's clock runs apart from the host's in so short a trace:
    # read every op, in a window of its own
    w = tr.Ev("w", min(o.start for o in ops), max(o.end for o in ops))
    r = ph.reduce_events(ops, host + [w], names, "w")
    red = tr.reduce_events([tr.Ev(o.name, o.start, o.end, o.device)
                            for o in ops], host + [w], "w")
    assert r["phase_s"]["sfl.server_tau"] > r["phase_s"][
        "sfl.client_forward"] > 0
    assert abs(sum(r["phase_s"].values())
               - (red["matmul_s"] + red["other_s"])) < 1e-12
