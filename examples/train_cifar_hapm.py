"""End-to-end driver: train the paper's CNN through the full pipeline
(fp32 -> int8 QAT -> HAPM gradual group pruning), with checkpoint/resume,
then price the result on all three boards.

A few hundred steps by default (CPU container); --paper restores the
paper's full protocol. Run:
    PYTHONPATH=src python examples/train_cifar_hapm.py [--epochs 4] [--paper]
"""
import argparse
import dataclasses
import os
import sys

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.accel import BOARDS, simulate
from repro.core.masks import global_sparsity
from repro.data.synthetic import SyntheticCifar
from repro.models import cnn

from benchmarks import cnn_training as CT


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--train-size", type=int, default=2048)
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--hapm-sparsity", type=float, default=0.5)
    ap.add_argument("--sparse-training", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run HAPM epochs after the first pruning step "
                         "through the block-sparse kernels (custom VJP)")
    args = ap.parse_args(argv)

    if args.paper:
        ds = SyntheticCifar(num_train=50000, num_test=10000)
        e = (200, 100, 60)
    else:
        ds = SyntheticCifar(num_train=args.train_size, num_test=512)
        e = (args.epochs + 2, args.epochs, args.epochs)
    steps = sum(e) * (ds.num_train // 128)
    print(f"training ~{steps} steps total on {ds.num_train} images\n")

    m1 = CT.train_variant("fp32", ds, e[0])
    m2 = CT.train_variant("int8", ds, e[1], init_from=m1)
    # sparse_training: once HAPM has pruned (epoch 1 onward), fwd+bwd run
    # through the block-sparse Pallas kernels (custom VJP) — per-epoch
    # wall-clock is printed next to each epoch's loss above
    m4 = CT.train_variant("hapm", ds, e[2], init_from=m2,
                          hapm_sparsity=args.hapm_sparsity,
                          sparse_training=args.sparse_training)

    print(f"\nfp32 acc={m1.test_accuracy:.3f} | int8 acc={m2.test_accuracy:.3f} "
          f"| HAPM acc={m4.test_accuracy:.3f} "
          f"(weight sparsity {global_sparsity(m4.masks):.2f})")

    print("\naccelerator pricing (DSB on):")
    imgs = jnp.asarray(ds.test_x[:256])
    labels = jnp.asarray(ds.test_y[:256])
    for name, board in BOARDS.items():
        r2 = simulate(m2.params, m2.state, m2.cfg, board, imgs, labels)
        r4 = simulate(m4.params, m4.state, m4.cfg, board, imgs, labels)
        print(f"  {name:>24}: int8 {r2.mean_time_per_image_s*1e3:6.2f} ms -> "
              f"HAPM {r4.mean_time_per_image_s*1e3:6.2f} ms "
              f"({r2.mean_time_per_image_s/r4.mean_time_per_image_s:.2f}x)")

    # --- execute the pruning through the Pallas DSB kernel ----------------
    # (interpret mode on CPU; plans come from the pruned weights' zero
    #  slabs, at the same n_cu=12 granularity as the board being compared)
    print("\nexecuted sparse inference (block-sparse Pallas path):")
    board12 = BOARDS["zedboard_100mhz_72dsp"]          # n_cu = 12
    r12 = simulate(m4.params, m4.state, m4.cfg, board12)
    # quantized=True: every bound conv runs int8 Q2.5×Q3.4 codes with
    # int32 accumulation — the same arithmetic the QAT forward fakes in
    # f32, so the parity below is exact on codes, not a float tolerance
    exec_ = cnn.bind_execution(
        m4.params, m4.cfg,
        spec=cnn.ExecSpec(quantized=True, n_cu=board12.n_cu))
    small = imgs[:2]
    dense_logits, _ = cnn.apply(m4.params, m4.state, small, m4.cfg)
    sparse_logits, _ = cnn.apply(m4.params, m4.state, small, m4.cfg, sparse=exec_)
    err = float(jnp.max(jnp.abs(sparse_logits - dense_logits)))
    executed, dense_steps = exec_.step_counts(m4.cfg, batch=1)
    print(f"  dispatched grid steps/image: {executed}/{dense_steps} "
          f"({executed / dense_steps:.2f} of dense) | "
          f"DSB cycle ratio {r12.dsb_cycle_ratio:.2f} | "
          f"max |sparse - dense| = {err:.2e}")
    # executed-int8 vs QAT parity: the int32 kernels and the f32 fake-quant
    # forward are the same exact integer arithmetic, so the logits must be
    # bitwise-identical arrays (strictly stronger than any code comparison).
    # Precondition: the f32 reference is itself exact (K·127² < 2^24 — true
    # for the paper CNN, max K = 3·3·64; guarded so config growth fails
    # with the right message, not a bogus "int8 diverged")
    from repro.core import quant as Q
    assert Q.f32_parity_is_exact(max(3 * 3 * c for c in m4.cfg.widths)), \
        "config outgrew the f32-exactness bound — compare with a tolerance"
    assert bool(jnp.array_equal(sparse_logits, dense_logits)), err
    code_delta = int(jnp.max(jnp.abs(Q.to_int(sparse_logits, Q.Q3_4)
                                     - Q.to_int(dense_logits, Q.Q3_4))))
    hbm_q = exec_.hbm_bytes(m4.cfg, batch=1)
    hbm_f = exec_.hbm_bytes(m4.cfg, batch=1, operand_bytes=4)
    print(f"  executed-int8 vs QAT logits: exact on codes "
          f"(max |Δ Q3.4 code| = {code_delta}) | "
          f"int8 operand HBM bytes/image {hbm_q} "
          f"({hbm_q / hbm_f:.2f}x of f32 operands)")

    # --- and the training direction: gradients through the kernels --------
    # dense reference and sparse path must differentiate the SAME loss,
    # i.e. through apply_masks (the train step masks before the forward);
    # the raw dense conv has nonzero grads at pruned positions by design
    import jax

    from repro.core import apply_masks

    texec = cnn.bind_execution(
        m4.params, m4.cfg, spec=cnn.ExecSpec(trainable=True, n_cu=board12.n_cu))
    tbatch = {"x": small, "y": labels[:2]}

    def loss(p, sparse):
        l, _ = CT._loss_fn(apply_masks(p, m4.masks), m4.state, tbatch,
                           m4.cfg, sparse)
        return l

    gd = jax.grad(lambda p: loss(p, None))(m4.params)
    gs = jax.grad(lambda p: loss(p, texec))(m4.params)
    gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(gd), jax.tree.leaves(gs)))
    pruned_max = max(
        float(jnp.max(jnp.abs(g * (1 - m)))) if m is not None else 0.0
        for g, m in zip(jax.tree.leaves(gs),
                        jax.tree.leaves(m4.masks, is_leaf=lambda x: x is None)))
    print(f"  sparse-kernel training grads: max |dense - sparse| = {gerr:.2e} "
          f"| max pruned-group grad = {pruned_max:.2e}")
    assert gerr <= 1e-4, f"gradient parity broke: {gerr}"
    assert pruned_max == 0.0, "pruned groups must get exactly-zero gradients"


if __name__ == "__main__":
    main()
