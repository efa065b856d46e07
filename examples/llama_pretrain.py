"""Llama pretraining recipe — the BASELINE.md north-star config, runnable.

Composes the whole distributed stack: ProcessMesh (dp x mp, dp x ep, or
fsdp) -> shard_llama placements -> bf16 auto_cast -> optional recompute
on every decoder layer -> jit.to_static compiled train step with
DONATED ids/labels buffers -> double-buffered async host->device
prefetch (io.DevicePrefetcher; input_stall_frac reported) ->
throughput/MFU accounting -> distributed checkpoint save/resume. The
loss rides the chunked fused cross-entropy lm-head by default
(PADDLE_TPU_FUSED_CE=0 restores the materialized logits path);
``--moe E`` selects the mixture-of-experts FFN and ``--ep`` shards the
stacked expert weights over the second mesh axis (expert parallelism).

CPU sanity (8 virtual chips):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/llama_pretrain.py --config tiny --mesh 2x4 --steps 8

Expert-parallel MoE pretraining (same virtual mesh):
  ... python examples/llama_pretrain.py --config tiny --mesh 2x4 \
      --moe 4 --ep --steps 8

TPU single chip:
  python examples/llama_pretrain.py --config 0.5b --steps 20 --amp
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.distributed import (  # noqa: E402
    ProcessMesh, save_state_dict, load_state_dict, recompute)
from paddle_tpu.models import (  # noqa: E402
    LlamaConfig, LlamaForCausalLM, shard_llama, tiny_llama_config)

CONFIGS = {
    "tiny": lambda: tiny_llama_config(num_hidden_layers=2),
    "0.5b": lambda: LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=4096),
    "8b": lambda: __import__("paddle_tpu.models", fromlist=["m"])
    .llama3_8b_config(),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tiny", choices=sorted(CONFIGS))
    ap.add_argument("--mesh", default=None,
                    help="AxB = dp x mp mesh over visible devices; "
                         "'fsdp' = 1-D fully-sharded; default single")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--amp", action="store_true", help="bf16 autocast")
    ap.add_argument("--recompute", action="store_true",
                    help="checkpoint every decoder layer")
    ap.add_argument("--moe", type=int, default=0, metavar="E",
                    help="mixture-of-experts FFN with E experts "
                         "(LlamaMoEMLP, dropless top-k routing)")
    ap.add_argument("--moe-top-k", type=int, default=2)
    ap.add_argument("--ep", action="store_true",
                    help="with --mesh AxB and --moe: the second mesh "
                         "axis becomes 'ep' — expert-parallel sharding "
                         "of the stacked [E, ...] expert weights "
                         "(router replicated, GSPMD XLA grouped path)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", default=None,
                    help="flat binary int32 token file (io.TokenFeed, "
                         "C++ prefetch); default: synthetic random ids")
    args = ap.parse_args()

    import jax
    paddle.seed(0)
    cfg = CONFIGS[args.config]()
    if args.moe:
        cfg.moe_num_experts = args.moe
        cfg.moe_top_k = args.moe_top_k
    seq = args.seq or (16 if args.config == "tiny" else 2048)
    model = LlamaForCausalLM(cfg)

    mesh = None
    if args.mesh == "fsdp":
        mesh = ProcessMesh(np.arange(len(jax.devices())),
                           dim_names=["fsdp"])
        shard_llama(model, mesh, tp_axis=None, fsdp_axis="fsdp")
    elif args.mesh:
        dp, mp = (int(v) for v in args.mesh.split("x"))
        if args.ep:
            if not args.moe:
                ap.error("--ep needs --moe (expert weights to shard)")
            mesh = ProcessMesh(np.arange(dp * mp).reshape(dp, mp),
                               dim_names=["dp", "ep"])
            shard_llama(model, mesh, tp_axis=None, ep_axis="ep")
        else:
            mesh = ProcessMesh(np.arange(dp * mp).reshape(dp, mp),
                               dim_names=["dp", "mp"])
            shard_llama(model, mesh, tp_axis="mp")
    print(f"config={args.config} params={model.num_params():,} "
          f"mesh={args.mesh or 'single'}{'(ep)' if args.ep else ''} "
          f"seq={seq} batch={args.batch} amp={args.amp} "
          f"recompute={args.recompute} moe={args.moe or 'dense'}")

    if args.recompute:
        # wrap each decoder layer: activations re-derive in backward
        # (recompute() sees the bound method's owning Layer, so layer
        # params keep their gradients)
        for layer in model.model.layers:
            orig = type(layer).forward.__get__(layer)
            layer.forward = (lambda f: lambda *a, **k:
                             recompute(f, *a, **k))(orig)

    opt = paddle.optimizer.AdamW(learning_rate=args.lr, weight_decay=0.1,
                                 parameters=model.parameters())

    def step_fn(ids, labels):
        if args.amp:
            with paddle.amp.auto_cast(dtype="bfloat16"):
                loss, _ = model(ids, labels)
        else:
            loss, _ = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # the step's ids/labels buffers are donated to XLA: every call gets
    # a FRESH device batch from the prefetcher below, so donation is
    # safe and the input HBM becomes workspace after the embedding read
    compiled = paddle.jit.to_static(step_fn, state=[model, opt],
                                    warmup="once", donate_inputs=True)

    rng = np.random.RandomState(0)
    if args.data:
        from paddle_tpu.io import TokenFeed
        source = TokenFeed(args.data, sample_elems=seq + 1,
                           batch_size=args.batch, dtype=np.int32, seed=0)
    else:
        # own stream: the prefetch worker draws concurrently with the
        # main thread's warmup draw from `rng` — sharing one state
        # would make seeded runs scheduler-dependent
        feed_rng = np.random.RandomState(1)

        def synthetic():
            while True:
                yield feed_rng.randint(
                    0, cfg.vocab_size,
                    (args.batch, seq + 1)).astype(np.int64)
        source = synthetic()

    # double-buffered async host->device prefetch: the next batch's H2D
    # copy overlaps the current compiled step. With a dp mesh the
    # prefetcher puts straight to the sharded layout.
    from paddle_tpu.io import DevicePrefetcher
    put = None
    if mesh is not None and "dp" in mesh.dim_names:
        from jax.sharding import NamedSharding, PartitionSpec
        ns = NamedSharding(mesh.to_jax_mesh(),
                           PartitionSpec("dp", None))
        put = lambda a: jax.device_put(a, ns)  # noqa: E731

    def split(ids):
        ids = ids.astype(np.int64)
        return (np.ascontiguousarray(ids[:, :-1]),
                np.ascontiguousarray(ids[:, 1:]))

    feed = DevicePrefetcher(source, transform=split, put=put)

    def batch():
        x, y = next(feed)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    # eager warmup on a tiny shape (materializes optimizer state without
    # paying a full-size eager pass); the real shape compiles directly
    wseq = min(seq, 128)
    wids = rng.randint(0, cfg.vocab_size, (1, wseq + 1)).astype(np.int64)
    compiled(paddle.to_tensor(wids[:, :-1]), paddle.to_tensor(wids[:, 1:]))

    # resume AFTER warmup: optimizer accumulators exist, so the full
    # (weights + moments) training state restores — not just weights
    if args.resume and args.ckpt_dir and os.path.exists(
            os.path.join(args.ckpt_dir, "metadata_p0.json")):
        load_state_dict({"model": model.state_dict(),
                         "opt": opt.state_dict()}, args.ckpt_dir)
        print(f"resumed model+optimizer from {args.ckpt_dir}", flush=True)

    flops_step = model.flops_per_token(seq) * args.batch * seq
    t0 = time.perf_counter()
    last_t = t0
    feed.mark()
    for i in range(args.steps):
        loss = compiled(*batch())
        lossf = float(loss)   # host sync
        now = time.perf_counter()
        dt = now - last_t
        last_t = now
        tps = args.batch * seq / dt
        print(f"step {i:4d} loss {lossf:8.4f} {dt * 1e3:8.1f} ms "
              f"{tps:10.0f} tok/s {flops_step / dt / 1e12:6.2f} TFLOP/s",
              flush=True)
    stall, wall = feed.mark()
    print(f"input_stall_frac {stall / max(wall, 1e-9):.3f} "
          f"({stall * 1e3:.1f} ms blocked on input over "
          f"{wall:.2f} s)", flush=True)
    feed.close()

    if args.ckpt_dir:
        save_state_dict({"model": model.state_dict(),
                         "opt": opt.state_dict()}, args.ckpt_dir)
        print(f"checkpoint (model+optimizer) written to {args.ckpt_dir}")


if __name__ == "__main__":
    main()
