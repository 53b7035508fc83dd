"""The trace reduction on a hand-built trace: busy time is the union of
operation intervals inside the window, operations are summed per name,
idle gaps go to the host span that covers most of them, and chips are
averaged."""
import pytest

from bench import trace as T

E = T.Event


def _host():
    return [E("bench.window", 10.0, 20.0),
            E("bench.step", 10.0, 14.0),
            E("bench.collect", 14.0, 15.0),
            E("bench.idle", 15.0, 20.0)]


def test_union_and_gaps():
    assert T.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert T.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert T.clip([(0, 2), (4, 6), (7, 9)], 1, 5) == [(1, 2), (4, 5)]


def test_reduce_one_chip():
    ops = [E("fusion.1", 9.0, 11.0),        # half outside the window
           E("paged_attention", 11.0, 13.0),
           E("fusion.2", 12.5, 13.5),       # overlaps the kernel
           E("fusion.3", 16.0, 16.5)]
    r = T.reduce({"/device:TPU:0": ops}, _host())
    assert r.window_s == pytest.approx(10.0)
    # busy: [10, 13.5] and [16, 16.5]
    assert r.busy_s == pytest.approx(4.0)
    assert r.ops == pytest.approx({"fusion.1": 1.0, "paged_attention": 2.0,
                                   "fusion.2": 1.0, "fusion.3": 0.5})
    # idle: [13.5, 16] (0.5 in step, 1 in collect, 1 in idle) -> collect
    # ties with idle on overlap, the first wins; [16.5, 20] -> idle
    assert sum(r.idle_by_span.values()) == pytest.approx(6.0)
    assert r.idle_by_span["bench.idle"] == pytest.approx(3.5)
    assert r.top_ops(1) == [["paged_attention", 2.0]]


def test_a_kernel_is_found_by_its_label():
    # XLA names the kernel's operation after the call around it; its
    # label keeps the op_name of the pallas_call
    ops = [E("closed_call.10", 11.0, 12.0), E("closed_call.10", 13.0, 13.5),
           E("fusion.2", 12.0, 13.0)]
    labels = {"closed_call.10": "jit(<unknown>)/while/body/closed_call/"
                                "pallas_call tpu_custom_call",
              "fusion.2": "jit(<unknown>)/while/body/dot_general"}
    r = T.reduce({"/device:TPU:0": ops}, _host(), labels)
    assert r.time_of(("paged_attention", "pallas_call")) == pytest.approx(1.5)
    assert r.time_of(("dot_general",)) == pytest.approx(1.0)
    assert r.time_of(("absent",)) == 0


def test_breakdown_names_keep_handle_opcode_and_target():
    # operation names as a v5e trace gives them (cut short here)
    kernel = ('%closed_call.10 = bf16[8,32,32,1,96]{4,3,2,1,0:T(2,128)(2,1)'
              'S(1)} custom-call(s32[1]{0:T(128)} %dynamic_slice.88), '
              'custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={s32[1]{0}}')
    loop = ('%while.2 = (s32[]{:T(128)}, bf16[8,32,3072]{2,1,0:T(8,128)(2,1)'
            'S(1)}) while((s32[]{:T(128)}, bf16[8,32,3072]{2,1,0:T(8,128)'
            '(2,1)S(1)}) %tuple.5), condition=%cond, body=%body')
    copy = ('%copy.61 = bf16[1,3072,3072]{1,2,0:T(8,128)(2,1)S(1)} copy('
            'bf16[1,3072,3072]{2,1,0:T(8,128)(2,1)S(1)} %fusion.7)')
    assert T.short(kernel) == "%closed_call.10 custom-call tpu_custom_call"
    assert T.short(loop) == "%while.2 while"
    assert T.short(copy) == "%copy.61 copy"
    assert T.short("fusion.3") == "fusion.3"
    r = T.reduce({"/device:TPU:0": [E(loop, 10.0, 14.0), E(kernel, 11.0, 12.0)]},
                 _host())
    assert r.top_ops() == [["%while.2 while", 4.0],
                           ["%closed_call.10 custom-call tpu_custom_call", 1.0]]
    assert r.time_of(("tpu_custom_call",)) == pytest.approx(1.0)


def test_reduce_averages_chips():
    a = [E("k", 10.0, 20.0)]
    b = [E("k", 10.0, 15.0)]
    r = T.reduce({"/device:TPU:0": a, "/device:TPU:1": b}, _host())
    assert r.chips == 2
    assert r.busy_s == pytest.approx(7.5)
    assert r.ops["k"] == pytest.approx(7.5)
    assert sum(r.idle_by_span.values()) == pytest.approx(2.5)


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        T.reduce({}, _host())
    with pytest.raises(ValueError):
        T.reduce({"/device:TPU:0": [E("k", 0, 1)]}, _host()[1:])
