"""The prices of ``ai21-jamba2-3b``'s two Mamba-1 kernels, from their
calls as the compiled program prints them (the operand and result
shapes of the benchmark's programs, described-v5e compiles): the decode
step ``ssm_step_selective`` by ``costs_granite.ssm_step_call``, the
price ``ssm_step_roofline`` reads, and the chunk call's ``ssm_scan`` by
``costs_jamba.ssm_scan_call``.
"""

import pytest

POOL = "f32[257,26,40,16,128]"
#: a 256-lane decode call's step of one layer: the lanes' slots and
#: fresh flags, [Delta; x] a channel, [B, C] a lane, the decays, the pool
STEP_CALL = (
    f"%ssm_step_selective.26 = (f32[256,40,128]{{2,1,0:T(8,128)}}, {POOL}"
    "{4,3,2,1,0:T(8,128)}) custom-call(s32[256]{0:T(256)} %slots, "
    "s32[256]{0:T(256)} %fresh, f32[256,2,40,128]{3,2,1,0:T(8,128)} %dx, "
    "f32[256,16,2]{2,1,0:T(8,128)} %bc, f32[40,16,128]{2,1,0:T(8,128)} "
    f"%a, {POOL}{{4,3,2,1,0:T(8,128)}} %pool), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
#: a one-lane chunk call of 1024 rows, one layer: the lane's state, the
#: rows' Delta, x and z, B and C turned, the decays, D
SCAN_CALL = (
    "%ssm_scan.26 = (bf16[1,1024,5120]{2,1,0:T(8,128)(2,1)}, "
    "f32[1,40,16,128]{3,2,1,0:T(8,128)}) custom-call("
    "f32[1,40,16,128]{3,2,1,0:T(8,128)} %s, f32[1,1024,5120]{2,1,0} %d, "
    "f32[1,1024,5120]{2,1,0} %x, bf16[1,1024,5120]{2,1,0} %z, "
    "f32[1,64,16,16]{3,2,1,0} %bt, f32[1,64,16,16]{3,2,1,0} %ct, "
    "f32[40,16,128]{2,1,0} %a, f32[16,5120]{1,0} %skip), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={}')

STATE = 40 * 16 * 128 * 4                 # one lane's state of one layer


def _price(cost, call):
    from benchmark import trace_reduce

    _, _, result = trace_reduce.parse_hlo(call)
    operands = call.partition(result)[2].partition("custom_call_target")[0]
    return cost(trace_reduce.shapes(result), trace_reduce.shapes(operands))


def test_the_selective_step_is_priced_by_its_lanes_states():
    """Granite's price reads the new step's call right: the pool is the
    operand with five dimensions, the lanes lead the first float32
    operand with four, each lane's state of one layer is read once and
    written once (2 x 256 x 327,680 B, not the 2.19 GB pool) and every
    other operand and result once. Its operations are 6 E N a lane where
    the kernel does ~8 (the decay's product and exp besides), a bound
    over two orders under the bytes' at HBM's rate: the share it gives is
    the bytes' share, and the cell joins ``ssm_step_roofline``."""
    from benchmark import costs_granite

    flops, nbytes = _price(costs_granite.ssm_step_call, STEP_CALL)
    lanes, E, N = 256, 5120, 16
    assert flops == 6.0 * lanes * E * N
    others = (2 * lanes * 4 + lanes * 2 * E * 4 + lanes * N * 2 * 4
              + E * N * 4 + lanes * E * 4)
    assert nbytes == 2 * lanes * STATE + others
    # the state is nine tenths of what a call must move
    assert 0.9 < 2 * lanes * STATE / nbytes < 0.95
    assert flops / 197e12 < 1e-2 * nbytes / 819e9


def test_the_scan_is_priced_by_its_rows():
    """``costs_jamba.ssm_scan_call``: the lane's state read and written
    once, the rows' Delta and x (float32), z and y (bf16), B and C
    turned, the decays and D once; 8 E N operations a row."""
    from benchmark import costs_jamba

    flops, nbytes = _price(costs_jamba.ssm_scan_call, SCAN_CALL)
    rows, E, N = 1024, 5120, 16
    assert flops == 8.0 * rows * E * N
    assert nbytes == (2 * STATE + rows * E * (4 + 4 + 2 + 2)
                      + 2 * rows * N * 4 + E * N * 4 + 16 * E * 4)
    # bound by HBM in peaks.json's terms: ~79 us of bytes, ~3.4 us of
    # the MXU's peak, which the scan does not use
    assert flops / 197e12 < nbytes / 819e9 == pytest.approx(7.86e-5,
                                                            rel=0.01)
