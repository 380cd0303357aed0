"""Char-level TransformerLM: train + sample.

No reference equivalent (SINGA's examples stop at Char-RNN,
`examples/rnn/train.py`); this is the transformer twin of that
workload on the native flagship model — train a decoder-only LM on a
character corpus, then sample from it with the jitted KV-cache
decoder (`TransformerLM.generate`).

Run:  python train_lm.py [--steps 200] [--sample 120]
"""
import argparse
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(_HERE, "..", "..")))

from singa_tpu import device, opt, tensor  # noqa: E402
from singa_tpu.models.transformer import TransformerLM  # noqa: E402

# a small built-in corpus (no downloads in this environment)
CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump! "
    "sphinx of black quartz, judge my vow. "
) * 64


def batches(text, ids_of, seq, batch, steps, seed=0):
    data = np.array([ids_of[c] for c in text], np.int32)
    rs = np.random.RandomState(seed)
    for _ in range(steps):
        starts = rs.randint(0, len(data) - seq - 1, batch)
        x = np.stack([data[s:s + seq] for s in starts])
        y = np.stack([data[s + 1:s + seq + 1] for s in starts])
        yield x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sample", type=int, default=120)
    ap.add_argument("--temperature", type=float, default=0.8)
    a = ap.parse_args()
    if a.steps < 1:
        ap.error("--steps must be >= 1 (the first batch compiles the "
                 "model)")
    max_len = max(256, a.seq)
    if len("the ") + a.sample > max_len:
        ap.error(f"--sample {a.sample} exceeds the model context "
                 f"({max_len} incl. the 4-char prompt)")

    chars = sorted(set(CORPUS))
    ids_of = {c: i for i, c in enumerate(chars)}
    vocab = len(chars)
    print(f"corpus {len(CORPUS)} chars, vocab {vocab}")

    dev = device.create_tpu_device()
    dev.SetRandSeed(1)
    # Default-on Pallas kernel tier on real TPU (VERDICT r4 next #3):
    # the fused softmax-xent kernel (1.80x XLA at LM logit shapes,
    # benchmarks/PALLAS_BENCH.md) engages through the model's
    # (B*S, V)-logits loss; flash attention engages when the sequence
    # clears its crossover. SINGA_TPU_PALLAS=0 opts out.
    import jax

    from singa_tpu.ops import pallas_kernels as pk

    if (jax.default_backend() == "tpu"
            and os.environ.get("SINGA_TPU_PALLAS", "1") != "0"):
        pk.enable(True)
        print("pallas tier on (fused softmax-xent + flash attention)")
    m = TransformerLM(vocab, d_model=128, num_heads=4, num_layers=3,
                      max_len=max_len)
    m.set_optimizer(opt.SGD(
        lr=opt.WarmupWrapper(opt.CosineDecay(0.3, a.steps), 20),
        momentum=0.9))

    first = True
    for step, (x, y) in enumerate(
            batches(CORPUS, ids_of, a.seq, a.batch, a.steps)):
        tx = tensor.from_numpy(x, device=dev)
        ty = tensor.from_numpy(y, device=dev)
        if first:
            m.compile([tx], is_train=True, use_graph=True)
            first = False
        _, loss = m(tx, ty)
        if step % 20 == 0 or step == a.steps - 1:
            print(f"step {step:4d}  loss {float(loss.to_numpy()):.4f}")

    m.eval()
    prompt = "the "
    ids = np.array([[ids_of[c] for c in prompt]], np.int32)
    out = m.generate(ids, a.sample, temperature=a.temperature,
                     top_k=8, seed=0)
    text = "".join(chars[i] for i in out[0])
    print(f"\nsample (T={a.temperature}, top_k=8):\n{text}")


if __name__ == "__main__":
    main()
