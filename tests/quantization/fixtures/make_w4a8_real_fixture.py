"""Generate the checked-in tiny REAL-QUANTIZED fixture
(w4a8_real_tiny.npz) for the round-7 real-weights drift gate
(round-5 review item #6, scoped to zero-egress: everything downstream of a hub
download runs for real — genuine AutoGPTQ group-quantization math over
LLM-shaped weight matrices, not random bit packings).

Weight realism: rows drawn at 1/sqrt(K) scale with ~1% outlier
channels at 8x (the heavy-tailed per-channel structure that makes
activation quantization the risky approximation), quantized with the
ACTUAL asymmetric 4-bit group math (f16-rounded scales, z-1 storage —
the same convention tests/engine/test_quantized_checkpoint_e2e.py
proves against transformers). Activations carry an RMS-normalized
profile with token-level spikes.

Run `python tests/quantization/fixtures/make_w4a8_real_fixture.py` to
regenerate; the npz is deterministic (seeded) so a regeneration is a
no-op diff."""
import os

import numpy as np

GS, BITS = 128, 4
# K=384 exercises the 3-k-tile tail; N=384 gives the streamed grid 3
# column runs (parity-plane reuse), N=512 a single-run column.
LAYERS = [("qkv", 384, 512), ("down", 512, 384)]
M = 24          # decode-burst-sized activation rows (m <= 64: stream)
SEED = 1234


def quantize_gptq_group(w: np.ndarray):
    """[out, in] f32 -> AutoGPTQ v1 tensors + the dequantized weight
    (same math as the e2e checkpoint test, at the kernel-native
    group size)."""
    out_f, in_f = w.shape
    G = in_f // GS
    wg = w.reshape(out_f, G, GS)
    wmax, wmin = wg.max(-1), wg.min(-1)
    scale = np.maximum((wmax - wmin) / 15.0, 1e-8)
    scale = scale.astype(np.float16).astype(np.float32)
    zero = np.clip(np.round(-wmin / scale), 0, 15)
    q = np.clip(np.round(wg / scale[..., None]) + zero[..., None],
                0, 15).astype(np.int64)
    deq = ((q - zero[..., None]) * scale[..., None]) \
        .reshape(out_f, in_f).astype(np.float32)
    qT = q.reshape(out_f, in_f).T
    qweight = np.zeros((in_f // 8, out_f), np.int64)
    for p in range(8):
        qweight |= qT[p::8] << (4 * p)
    zT = zero.T.astype(np.int64) - 1            # v1 stores z-1
    qzeros = np.zeros((G, out_f // 8), np.int64)
    for p in range(8):
        qzeros |= (zT[:, p::8] & 0xF) << (4 * p)
    to_i32 = lambda a: np.ascontiguousarray(
        a.astype(np.uint64).astype(np.uint32)).view(np.int32)
    scales = np.ascontiguousarray(scale.T.astype(np.float16))
    return to_i32(qweight), to_i32(qzeros), scales, deq


def main() -> None:
    rs = np.random.RandomState(SEED)
    arrays = {}
    for name, K, N in LAYERS:
        w = rs.randn(N, K).astype(np.float32) / np.sqrt(K)
        outliers = rs.choice(K, max(1, K // 100), replace=False)
        w[:, outliers] *= 8.0                   # heavy-tailed channels
        qw, qz, sc, _ = quantize_gptq_group(w)
        # deq is NOT stored: the drift test re-derives the oracle with
        # its own independent unpack, so the fixture stays ~250 KiB.
        arrays[f"{name}.qweight"] = qw
        arrays[f"{name}.qzeros"] = qz
        arrays[f"{name}.scales"] = sc
        x = rs.randn(M, K).astype(np.float32)
        x /= np.sqrt(np.mean(np.square(x), axis=1, keepdims=True))
        spikes = rs.choice(M * K, max(1, M * K // 200), replace=False)
        x.reshape(-1)[spikes] *= 6.0            # token-level outliers
        arrays[f"{name}.x"] = x
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "w4a8_real_tiny.npz")
    np.savez_compressed(out, **arrays)
    print(f"wrote {out} "
          f"({os.path.getsize(out) / 1024:.0f} KiB)")


if __name__ == "__main__":
    main()
