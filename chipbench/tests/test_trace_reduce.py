"""trace_reduce on synthetic events named as a v5e trace names them, and
on a trace recorded here on the CPU."""
import jax
import jax.numpy as jnp

from chipbench import trace_reduce as tr

# names as the "XLA Ops" line of a TPU v5 lite trace gives them
DOT = ("%fusion.8 = (bf16[]{:T(256)}, bf16[4096,2048]{1,0:T(8,128)(2,1)S(1)}) "
       "fusion(bf16[4096,2048]{1,0:T(8,128)(2,1)S(1)} %copy.15, "
       "bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.91), "
       "kind=kOutput, calls=%fused_computation.1.clone.clone")
LOOP = ("%fusion.3 = bf16[2048,2048]{1,0:T(8,128)(2,1)} fusion(bf16[2048,2048]"
        "{1,0:T(8,128)(2,1)} %p), kind=kLoop, calls=%fused_computation.3")
COPY = ("%copy.15 = bf16[4096,2048]{1,0:T(8,128)(2,1)S(1)} copy(bf16[4096,2048]"
        "{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.84)")
WHILE = ("%while.2 = (s32[]{:T(128)}, bf16[4096,2048]{1,0:T(8,128)(2,1)S(1)}) "
         "while((s32[]{:T(128)}, bf16[4096,2048]{1,0:T(8,128)(2,1)S(1)}) "
         "%tuple.27), condition=%region_2.4, body=%region_0.3")
KERNEL = ("%f.1 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(u32[8]"
          "{0:T(128)S(1)} %copy-done.4, f32[8]{0:T(128)S(1)} %copy-done.5, "
          "bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} %reshape.4), "
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          "{u32[8]{0}, f32[8]{0}, bf16[4096,1024]{1,0}}, "
          "frontend_attributes={kernel_metadata={}}")
ALLOC = ('%custom-call = bf16[4]{0:T(256)(128)(2,1)S(1)} custom-call(), '
         'custom_call_target="AllocateBuffer"')
D = "/device:TPU:0"


def test_opcode_and_classes():
    assert tr.opcode(DOT) == "fusion" and tr.is_matmul(DOT)
    assert tr.opcode(LOOP) == "fusion" and not tr.is_matmul(LOOP)
    assert tr.opcode(COPY) == "copy" and not tr.is_matmul(COPY)
    assert tr.opcode(WHILE) == "while"
    assert tr.opcode(KERNEL) == "custom-call"
    assert tr.kernel_family(KERNEL) == "zo_replay"
    assert tr.kernel_family(ALLOC) is None
    assert tr.kernel_family(DOT) is None
    assert tr.short(DOT) == "fusion.8 fusion kOutput"
    assert tr.short(KERNEL) == "f.1 custom-call tpu_custom_call"


def test_reduce_busy_split_gaps_and_window_clip():
    host = [tr.Ev("chipbench.window", 1000, 2000),
            tr.Ev("chipbench.stage", 1500, 1700),
            tr.Ev("$engine.py:1098 flush", 1400, 1800),
            tr.Ev("$<unknown> append", 1550, 1560)]
    dev = [tr.Ev(WHILE, 900, 1400, D),        # container: left out
           tr.Ev(DOT, 900, 1100, D),          # clipped to [1000, 1100)
           tr.Ev(LOOP, 1050, 1200, D),        # overlaps the dot
           tr.Ev(KERNEL, 1200, 1300, D),
           tr.Ev(COPY, 1850, 1900, D),
           tr.Ev(DOT, 2100, 2200, D)]         # after the window
    r = tr.reduce_events(dev, host, "chipbench.window")
    assert r["window_s"] == 1000e-9
    assert r["busy_s"] == 300e-9 + 50e-9      # [1000,1300) and [1850,1900)
    assert r["n_ops"] == 4
    assert r["matmul_s"] == 100e-9
    assert abs(r["other_s"] - 300e-9) < 1e-18
    assert r["kernel_s"] == {"zo_replay": 100e-9}
    assert r["top_gaps"][0] == ["chipbench.stage", 550e-9]   # [1300,1850)
    assert r["top_gaps"][1] == ["no host span", 100e-9]      # [1900,2000)
    assert r["top_ops"][0] == ["fusion.3 fusion kLoop", 150e-9]


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_reads_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    dev, host = tr.load(tr.find_xplane(str(tmp_path)))
    assert any(h.name == "chipbench.window" for h in host)
    r = tr.reduce_events(dev, host, "chipbench.window")
    assert r["window_s"] > 0 and r["busy_s"] == 0.0   # no TPU plane here
