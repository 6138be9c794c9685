"""Production training driver: federated stats-objective pretraining
(``--objective dcco|dvicreg|dwmse``, ``repro.objectives``) of any assigned
architecture (``--arch``), runnable end-to-end on CPU with smoke configs.

Three execution modes:
  * ``--mode engine``    — scan-compiled round engine (default): the whole
                           multi-round loop (sampling included) is ONE jitted
                           lax.scan program per metrics segment, with donated
                           carry and periodic checkpointing.
  * ``--mode fused``     — pod-style fused train step (one jit'd step ==
                           one federated round via the Appendix-A theorem;
                           what the dry-run lowers to the production mesh).
  * ``--mode protocol``  — the client-level federated simulator, one Python
                           dispatch per round (reference semantics; also the
                           baseline the engine is benchmarked against).

Example (CPU, reduced config, a few hundred rounds):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \\
      --smoke --rounds 200 --clients-per-round 16 --samples-per-client 2
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm, hierarchy, objectives as objectives_lib
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs.base import (DualEncoderConfig, TrainConfig, get_config,
                                get_dual_encoder_config)
from repro.core import buffer as buffer_lib
from repro.core import eval as eval_lib, fed_sim, round_engine
from repro.data import latency as latency_lib
from repro.data import partition as partition_lib
from repro.data import pipeline, synthetic
from repro.launch import steps as steps_lib
from repro.models import dual_encoder
from repro.optim import optimizers as opt_lib, schedules
from repro.retrieval import index as index_lib
from repro.server import drift as drift_lib
from repro.server import update as server_update_lib
from repro.sharding import maybe_initialize_distributed
from repro.utils import enable_compile_cache

# images per chunk of the probe's feature forward (make_probe_features)
PROBE_CHUNK = 1024


def build_dataset(cfg, args):
    if cfg.family == "resnet":
        imgs, labels = synthetic.synthetic_labeled_images(
            args.dataset_size, args.num_classes, image_size=cfg.image_size,
            noise=0.5, seed=args.seed)
        data = {"images": imgs}
        vocab = 0
    else:
        toks, labels = synthetic.synthetic_labeled_tokens(
            args.dataset_size, args.num_classes, args.seq_len,
            vocab=cfg.vocab_size, seed=args.seed)
        data = {"tokens": toks}
        vocab = cfg.vocab_size
    num_clients = max(args.dataset_size // args.samples_per_client, 4)
    if args.partition is not None:
        spec = partition_lib.PartitionSpec(
            args.partition,
            severity=1.0 if args.severity is None else args.severity)
    elif args.alpha is not None:
        # deprecated spelling; the PartitionSpec alias is bit-identical
        print("--alpha is deprecated; use --partition dirichlet "
              "--severity (see docs/architecture.md §15)", flush=True)
        spec = partition_lib.PartitionSpec("dirichlet", alpha=args.alpha)
    else:
        # legacy default: the paper's fully non-IID partition (alpha=0)
        spec = partition_lib.PartitionSpec("dirichlet", alpha=0.0)
    return pipeline.FederatedDataset.build(
        data, labels, num_clients=num_clients,
        samples_per_client=args.samples_per_client, partition=spec,
        seed=args.seed, vocab=vocab), labels


def _forbid_ignored_flags(ap, args, attrs, why: str) -> None:
    """Exit loudly when a flag was set but the selected mode/channel would
    silently ignore it (e.g. --quant-bits without --channel quant)."""
    flagged = ["--" + a.replace("_", "-") for a in attrs
               if getattr(args, a) != ap.get_default(a)]
    if flagged:
        raise SystemExit(f"{', '.join(flagged)} would be silently ignored: "
                         f"{why}")


def validate_flags(ap, args) -> None:
    if args.partition is not None and args.alpha is not None:
        raise SystemExit(
            "--alpha is the deprecated spelling of --partition dirichlet; "
            "pass one, not both (--alpha X == --partition dirichlet with "
            "the raw concentration X)")
    if args.partition is None:
        _forbid_ignored_flags(
            ap, args, ["severity"],
            "--severity maps onto --partition's strategy parameter; "
            "without --partition the legacy dirichlet(alpha) cut is used")
    elif args.severity is not None and not 0.0 <= args.severity <= 1.0:
        raise SystemExit(f"--severity {args.severity} must be in [0, 1]")
    if args.partition == "dirichlet_quantity" and args.mode == "fused":
        raise SystemExit(
            "--partition dirichlet_quantity yields variable-size clients "
            "(padded rows masked by per-client sizes); the fused pod step "
            "flattens the cohort without a mask — use --mode engine or "
            "protocol")
    if args.clusters:
        if args.mode != "engine":
            raise SystemExit(
                f"--clusters runs the cluster-aware round inside the scan "
                f"engine; --mode {args.mode} has no clustered body — use "
                f"--mode engine")
        if args.async_k:
            raise SystemExit(
                "--clusters with --async-k: the staleness buffer folds "
                "contributions into ONE server aggregate as they arrive; "
                "per-cluster aggregation needs the materialized "
                "synchronous cohort — drop one")
        if args.cohort_chunk:
            raise SystemExit(
                "--clusters with --cohort-chunk: cluster assignment reads "
                "the whole cohort's stats at once; the streamed cohort "
                "never materializes them — drop one")
        if args.scaffold:
            raise SystemExit(
                "--clusters with --scaffold: SCAFFOLD variates assume one "
                "shared broadcast model, the clustered round broadcasts "
                "per-cluster params — drop one")
        if args.stats_kernel != "off":
            raise SystemExit(
                "--clusters needs PER-CLIENT phase-1 stats for the "
                "k-means assignment; --stats-kernel aggregates the "
                "flattened cohort and never materializes them — drop one")
        if args.channel == "dp":
            raise SystemExit(
                "--clusters refuses --channel dp: per-cluster aggregates "
                "change the DP sensitivity, the accountant's epsilon "
                "would not cover the release — run DP on the global path")
        if args.edges and args.edges != args.clusters:
            raise SystemExit(
                f"--clusters {args.clusters} with --edges {args.edges}: "
                f"cluster ids route clients through their own edge, so "
                f"the tree needs exactly one edge per cluster "
                f"(--edges == --clusters)")
        if args.clusters > args.clients_per_round:
            raise SystemExit(
                f"--clusters {args.clusters} exceeds --clients-per-round "
                f"{args.clients_per_round}: every cluster needs a chance "
                f"of cohort members")
    else:
        _forbid_ignored_flags(
            ap, args, ["cluster_iters"],
            "--cluster-iters tunes the in-scan k-means of --clusters")
    if args.objective != "dcco":
        if args.mode == "fused":
            raise SystemExit(
                f"--objective {args.objective} needs the objective-"
                f"parametric round bodies; the fused pod step hardcodes "
                f"the CCO loss — use --mode engine or protocol")
        _forbid_ignored_flags(
            ap, args, ["lam"],
            f"--lam is the CCO off-diagonal weight; --objective "
            f"{args.objective} has its own hyperparameters")
    if args.channel != "quant":
        _forbid_ignored_flags(
            ap, args, ["quant_bits"],
            f"--quant-bits only applies to --channel quant "
            f"(got --channel {args.channel})")
    if args.channel not in ("quant", "int8"):
        _forbid_ignored_flags(
            ap, args, ["quant_kernel"],
            f"--quant-kernel only applies to the quantized channels "
            f"(got --channel {args.channel})")
    if args.channel != "dp":
        _forbid_ignored_flags(
            ap, args, ["dp_sigma", "dp_clip", "dp_delta"],
            f"DP flags only apply to --channel dp (got --channel "
            f"{args.channel})")
    if args.channel != "dropout" and not (args.edges
                                          and args.edge_channel == "dropout"):
        _forbid_ignored_flags(
            ap, args, ["dropout_p"],
            f"--dropout-p only applies to --channel dropout or an "
            f"--edge-channel dropout hop (got --channel {args.channel})")
    if args.mode != "engine":
        _forbid_ignored_flags(
            ap, args, ["stats_kernel", "chunk_rounds", "cohort_chunk"],
            f"--mode {args.mode} does not run the scan engine")
    if args.retrieval_eval:
        if args.mode != "engine":
            raise SystemExit(
                f"--retrieval-eval runs inside the scan engine's round "
                f"loop; --mode {args.mode} has no in-scan eval slot — "
                f"use --mode engine")
        if args.retrieval_every < 1:
            raise SystemExit(f"--retrieval-every {args.retrieval_every} "
                             f"must be >= 1")
        if args.retrieval_corpus < 10:
            raise SystemExit(
                f"--retrieval-corpus {args.retrieval_corpus} is smaller "
                f"than the largest reported cutoff (recall@10)")
        held_out = args.retrieval_corpus + args.retrieval_queries
        if held_out > args.dataset_size:
            raise SystemExit(
                f"--retrieval-corpus {args.retrieval_corpus} + "
                f"--retrieval-queries {args.retrieval_queries} = "
                f"{held_out} exceeds --dataset-size {args.dataset_size}")
    else:
        _forbid_ignored_flags(
            ap, args, ["retrieval_every", "retrieval_corpus",
                       "retrieval_queries", "retrieval_dtype"],
            "retrieval flags configure the --retrieval-eval loop")
    if args.async_k:
        if args.mode != "engine":
            raise SystemExit(
                f"--async-k buffers contributions inside the scan engine; "
                f"--mode {args.mode} runs strictly synchronous rounds "
                f"(the fused pod step and the protocol loop have no "
                f"buffered scheduler) — use --mode engine")
        if args.cohort_chunk:
            raise SystemExit(
                "--async-k with --cohort-chunk: the staleness buffer and "
                "the streamed cohort are two schedulers for the same "
                "round and are not composed — drop one")
        if args.channel == "dp":
            raise SystemExit(
                "--async-k refuses --channel dp: DP noise calibration "
                "across staleness-weighted multi-tick aggregates is "
                "undefined (repro.core.buffer) — run DP on the "
                "synchronous engine")
        if args.stats_kernel != "off":
            raise SystemExit(
                "--async-k scatters per-client contributions by arrival "
                "delay; --stats-kernel aggregates the flattened cohort "
                "and never materializes them — drop one")
        if not 1 <= args.async_k <= args.clients_per_round:
            raise SystemExit(
                f"--async-k {args.async_k} must be in [1, "
                f"--clients-per-round {args.clients_per_round}]")
    else:
        _forbid_ignored_flags(
            ap, args, ["staleness", "latency_tail"],
            "--staleness / --latency-tail shape the buffered "
            "(--async-k) engine's arrival model; the synchronous engine "
            "ignores them")
    if args.edges:
        if args.clients_per_round % args.edges and not args.clusters:
            raise SystemExit(
                f"--edges {args.edges} does not divide --clients-per-round "
                f"{args.clients_per_round}: edges are contiguous "
                f"equal-size client groups (unless --clusters routes "
                f"clients to edges by cluster id)")
        if args.channel == "dp":
            raise SystemExit(
                "--edges refuses a DP client hop: noise calibration and "
                "epsilon accounting across a two-level tree are undefined "
                "(repro.hierarchy) — drop --edges or use a flat --channel dp")
        if args.mode == "fused":
            raise SystemExit(
                "--edges models the client->edge->server wire; the fused "
                "pod step has no per-client wire — use --mode engine or "
                "protocol")
    else:
        _forbid_ignored_flags(
            ap, args, ["edge_channel"],
            "--edge-channel configures the edge->server hop of --edges")
    if args.cohort_chunk:
        if args.clients_per_round % args.cohort_chunk:
            raise SystemExit(
                f"--cohort-chunk {args.cohort_chunk} does not divide "
                f"--clients-per-round {args.clients_per_round}")
        if args.edges and args.cohort_chunk % max(
                args.clients_per_round // args.edges, 1):
            raise SystemExit(
                f"--cohort-chunk {args.cohort_chunk} does not hold whole "
                f"edges of {args.clients_per_round // args.edges} clients "
                f"(--edges {args.edges})")
        _forbid_ignored_flags(
            ap, args, ["scaffold", "stats_kernel"],
            "streaming rounds keep no cohort-resident state: SCAFFOLD "
            "slot variates and the flattened-cohort stats kernel both "
            "need the materialized cohort")
    if args.mode == "fused":
        if args.channel != "none":
            raise SystemExit(
                "--channel models the client uplink; the fused pod step "
                "has no per-client wire — use --mode engine or protocol")
        _forbid_ignored_flags(
            ap, args, ["server_opt", "fedprox_mu", "scaffold", "local_steps"],
            "the fused pod step hardcodes the FedOpt delegate with one "
            "local step — use --mode engine or protocol for server/drift "
            "strategies")
    if args.server_opt != "fedavg_sgd":
        _forbid_ignored_flags(
            ap, args, ["server_optimizer"],
            f"--server-opt {args.server_opt} builds its own server "
            f"optimizer; the base --server-optimizer is unused")
    if args.server_opt in ("fedavg_sgd", "fedavgm"):
        _forbid_ignored_flags(
            ap, args, ["server_tau"],
            "--server-tau only applies to the adaptive --server-opt "
            "strategies (fedadagrad / fedadam / fedyogi)")


def make_apply(cfg, de_cfg):
    def apply(p, batch):
        if isinstance(batch, dict) and "v1" in batch:
            leaf = "images" if batch["v1"].ndim >= 4 else "tokens"
            zf, _ = dual_encoder.encode(cfg, de_cfg, p, {leaf: batch["v1"]})
            zg, _ = dual_encoder.encode(cfg, de_cfg, p, {leaf: batch["v2"]})
            return zf, zg
        raise ValueError("unexpected batch structure")
    return apply


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Federated dual-encoder pretraining driver "
                    "(flags are grouped; see each group below)")
    ap.add_argument("--arch", default="resnet14-cifar")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--mode", choices=["engine", "fused", "protocol"],
                    default="engine")
    ap.add_argument("--objective", default="dcco",
                    choices=list(objectives_lib.OBJECTIVES),
                    help="stats objective (repro.objectives) trained by "
                         "the two-phase protocol: 'dcco' = the paper's "
                         "cross-correlation loss (5-stat payload, --lam); "
                         "'dvicreg' / 'dwmse' = VICReg / whitening-MSE "
                         "from 7 statistics (engine/protocol modes)")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients-per-round", type=int, default=16)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--seed", type=int, default=0)

    g = ap.add_argument_group(
        "data & partition",
        "synthetic dataset shape + how it is cut into client shards "
        "(repro.data.partition — strategies are registered data; "
        "severity in [0,1] is the one cross-strategy heterogeneity axis)")
    g.add_argument("--partition", default=None,
                   choices=list(partition_lib.PARTITIONS),
                   help="client partition strategy: 'iid' (shuffled "
                        "control), 'uniform' (class-stratified, most "
                        "homogeneous), 'label' (pathological shards: "
                        "round(C - severity*(C-1)) classes/client), "
                        "'dirichlet' (label skew, alpha = "
                        "10**(3-6*severity)), 'dirichlet_quantity' "
                        "(client SIZES ~ Dir(beta), labels IID). "
                        "Default: the legacy fully non-IID dirichlet "
                        "(alpha=0) partition")
    g.add_argument("--severity", type=float, default=None,
                   help="heterogeneity severity in [0,1] for --partition "
                        "(0 = homogeneous, 1 = maximally skewed; default "
                        "1.0). Each strategy maps it onto its own "
                        "parameter — see docs/architecture.md §15")
    g.add_argument("--alpha", type=float, default=None,
                   help="DEPRECATED: raw Dirichlet concentration (old "
                        "spelling; 0=non-IID, >=1e6=IID). Use "
                        "--partition dirichlet --severity instead; "
                        "--alpha keeps existing configs bit-identical")
    g.add_argument("--samples-per-client", type=int, default=2)
    g.add_argument("--seq-len", type=int, default=64)
    g.add_argument("--dataset-size", type=int, default=600)
    g.add_argument("--num-classes", type=int, default=5)

    g = ap.add_argument_group(
        "clustered aggregation",
        "cluster-aware server aggregation for heterogeneous populations "
        "(repro.cluster): cosine k-means on the phase-1 stats assigns "
        "cohort clients to clusters inside the round scan; each cluster "
        "keeps its own correlation target and server-update slot")
    g.add_argument("--clusters", type=int, default=0,
                   help="number of server-side client clusters (engine "
                        "mode; 0/1 = the global single-model path — "
                        "--clusters 1 is bit-identical to 0). With "
                        "--edges, each cluster routes through its own "
                        "edge (requires --edges == --clusters)")
    g.add_argument("--cluster-iters", type=int, default=2,
                   help="Lloyd iterations per round of the in-scan "
                        "k-means (warm-started from the previous round's "
                        "centroids, so a small count suffices)")

    g = ap.add_argument_group(
        "engine", "scan-compiled round engine knobs (--mode engine)")
    g.add_argument("--chunk-rounds", type=int, default=0,
                   help="rounds per scan segment (engine mode; 0=eval-every)")
    g.add_argument("--stats-kernel", choices=["off", "pallas", "interpret"],
                   default="off",
                   help="route phase-1 aggregate stats through the fused "
                        "Pallas kernel (engine mode; 'pallas' compiles it "
                        "on a TPU and interprets it elsewhere)")
    g.add_argument("--compute-dtype", default="float32",
                   choices=sorted(round_engine.COMPUTE_DTYPES),
                   help="encoder forward/backward compute dtype (engine "
                        "mode). 'bfloat16' halves activation traffic and "
                        "doubles MXU throughput; the Eq.-3 statistics "
                        "accumulation, parameters, and server state stay "
                        "float32 regardless (see docs/performance.md). On "
                        "a TPU, 'float32' matmuls and convolutions run at "
                        "the chip's default precision (one bfloat16 pass) "
                        "unless jax.default_matmul_precision says otherwise")
    g.add_argument("--cohort-chunk", type=int, default=0,
                   help="stream the cohort through each round in chunks "
                        "of this many clients (engine mode; peak memory "
                        "O(chunk) instead of O(cohort), unlocking "
                        "thousands of clients/round; 0 = materialized)")

    g = ap.add_argument_group(
        "communication", "client->server wire models (repro.comm) and "
        "the two-level aggregation tree (repro.hierarchy)")
    g.add_argument("--channel", default="none",
                   choices=["none", "dense", "int8", "quant", "dp",
                            "dropout"],
                   help="client->server communication channel "
                        "(repro.comm): 'none' = ideal lossless wire; "
                        "'int8' = 8-bit stochastic-rounding quantization; "
                        "'quant' = --quant-bits quantization; 'dp' = "
                        "clipped + Gaussian-noised aggregation; "
                        "'dropout' = Bernoulli client dropout")
    g.add_argument("--quant-bits", type=int, default=8,
                   help="wire width for --channel quant")
    g.add_argument("--quant-kernel", choices=["off", "pallas", "interpret"],
                   default="off",
                   help="route quantize->dequantize through the fused "
                        "Pallas kernel (kernels/quantize.py)")
    g.add_argument("--dp-sigma", type=float, default=1.0,
                   help="DP noise multiplier (--channel dp)")
    g.add_argument("--dp-clip", type=float, default=1.0,
                   help="per-client L2 clip norm (--channel dp)")
    g.add_argument("--dp-delta", type=float, default=1e-5,
                   help="target delta for the epsilon accountant")
    g.add_argument("--dropout-p", type=float, default=0.1,
                   help="per-round client dropout probability "
                        "(--channel dropout)")
    g.add_argument("--edges", type=int, default=0,
                   help="fan the cohort in through this many edge "
                        "aggregators (repro.hierarchy): clients -> edges "
                        "-> server, --channel on the client->edge hop and "
                        "--edge-channel on the edge->server hop, both "
                        "hops' bytes accounted (0 = flat aggregation)")
    g.add_argument("--edge-channel", default="dense",
                   choices=["dense", "int8", "dropout"],
                   help="edge->server hop channel for --edges ('dropout' "
                        "models a regional edge outage taking all its "
                        "clients down at once, p = --dropout-p)")

    g = ap.add_argument_group(
        "asynchrony", "semi-synchronous FedBuff-style scheduling "
        "(repro.core.buffer) and the straggler arrival model")
    g.add_argument("--async-k", type=int, default=0,
                   help="semi-synchronous FedBuff-style engine "
                        "(repro.core.buffer): apply the server update "
                        "once this many client contributions have "
                        "ARRIVED — contributions are staleness-weighted "
                        "and buffered as they land, so throughput is "
                        "bounded by the server fold rate, not the "
                        "slowest client (0 = synchronous rounds)")
    g.add_argument("--staleness", default="unit",
                   choices=list(buffer_lib.STALENESS_FNS),
                   help="staleness down-weight s(tau) of a contribution "
                        "arriving tau ticks after dispatch: 'unit' = no "
                        "down-weighting, 'poly' = (1+tau)^-1/2 (the "
                        "FedBuff choice), 'inv' = 1/(1+tau)")
    g.add_argument("--latency-tail", type=float, default=0.0,
                   help="heavy-tail straggler severity (Pareto exponent "
                        "of the persistent per-client arrival-delay "
                        "distribution, repro.data.latency); 0 = every "
                        "contribution arrives the tick it was dispatched")

    g = ap.add_argument_group(
        "retrieval eval", "periodic in-training retrieval eval "
        "(repro.retrieval, engine mode)")
    g.add_argument("--retrieval-eval", action="store_true",
                   help="periodic in-training retrieval eval "
                        "(repro.retrieval): encode a held-out corpus + "
                        "query split with the current params each "
                        "--retrieval-every rounds (inside the scan, via "
                        "the fused MIPS top-k search) and report "
                        "recall@{1,5,10} / MRR alongside the probe "
                        "(engine mode)")
    g.add_argument("--retrieval-every", type=int, default=5,
                   help="rounds between in-scan retrieval evals "
                        "(--retrieval-eval); skipped rounds emit NaN")
    g.add_argument("--retrieval-corpus", type=int, default=256,
                   help="held-out items indexed as the retrieval corpus")
    g.add_argument("--retrieval-queries", type=int, default=64,
                   help="held-out query items scored against the corpus")
    g.add_argument("--retrieval-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="storage dtype of the in-eval corpus embeddings "
                        "(bfloat16 halves index residency; scores still "
                        "accumulate in f32)")

    g = ap.add_argument_group(
        "server & client optimization",
        "server update strategies (repro.server) and client local "
        "training hyperparameters")
    g.add_argument("--server-optimizer", default="adam",
                   choices=["sgd", "adam", "lars"],
                   help="base repro.optim optimizer consumed by the "
                        "fedavg_sgd server strategy (ignored — and "
                        "rejected if set — for adaptive --server-opt)")
    g.add_argument("--server-opt", default="fedavg_sgd",
                   choices=list(server_update_lib.SERVER_UPDATES),
                   help="server update strategy (repro.server): "
                        "'fedavg_sgd' = the FedOpt delegate to "
                        "--server-optimizer (pre-existing behavior); "
                        "'fedavgm' = server momentum; 'fedadagrad' / "
                        "'fedadam' / 'fedyogi' = Reddi-style adaptive "
                        "server optimizers with --server-tau adaptivity")
    g.add_argument("--server-tau", type=float, default=1e-3,
                   help="adaptivity epsilon tau of the adaptive server "
                        "optimizers")
    g.add_argument("--fedprox-mu", type=float, default=0.0,
                   help="FedProx proximal coefficient mu on the client "
                        "local loss (0 = off; only bites at "
                        "--local-steps > 1)")
    g.add_argument("--scaffold", action="store_true",
                   help="SCAFFOLD control variates (per-cohort-slot) for "
                        "client-drift correction; the variate uplink is "
                        "routed through --channel")
    g.add_argument("--local-steps", type=int, default=1,
                   help="client local GD steps per round")
    g.add_argument("--server-lr", type=float, default=2e-3)
    g.add_argument("--client-lr", type=float, default=1.0)
    g.add_argument("--lam", type=float, default=5.0)
    g.add_argument("--micro", type=int, default=1)
    return ap


def make_probe_features(cfg, chunk: int = PROBE_CHUNK):
    """Jitted ``(tower_params, images) -> (N, d_model)`` ResNet features
    for the linear probe, encoded ``chunk`` images at a time: activation
    memory is O(chunk), not O(dataset) (a one-shot forward over 50,000
    CIFAR-sized images needs ~13 GB for the stem activations alone).
    ``images`` may be (N, H, W, C) or the dataset's staged (N, H*W*C)
    rows."""
    from repro.models import resnet as resnet_mod
    shape = (cfg.image_size, cfg.image_size, cfg.resnet_in_channels)

    def forward(tower, images):
        return index_lib.encode_corpus_chunked(
            lambda t, x: resnet_mod.resnet_forward(
                cfg, t, x.reshape((-1,) + shape)),
            tower, images, chunk=chunk, normalize=False)

    return jax.jit(forward)


def main(argv=None):
    """Run the trainer on ``argv`` (default: the command line). Returns
    ``{"history": per-round losses, "probe": final probe accuracy,
    "segment_seconds": wall seconds of each engine segment}`` (the last
    is empty outside ``--mode engine``; the first segment includes its
    compilation)."""
    # no-op unless the REPRO_COORDINATOR / REPRO_NUM_PROCESSES /
    # REPRO_PROCESS_ID launch contract is set (multi-host runs); must
    # happen before any jax device use
    maybe_initialize_distributed()
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args(argv)
    validate_flags(ap, args)

    objective = objectives_lib.get_objective(
        args.objective, **({"lam": args.lam} if args.objective == "dcco"
                           else {}))
    cfg = get_config(args.arch, smoke=args.smoke)
    de_cfg = DualEncoderConfig(
        proj_dims=(64, 64) if args.smoke else
        get_dual_encoder_config(args.arch).proj_dims,
        lambda_cco=args.lam)
    key = jax.random.PRNGKey(args.seed)
    params = dual_encoder.init_dual_encoder(key, cfg, de_cfg)
    sched = schedules.cosine_decay(args.server_lr, args.rounds)
    if args.server_opt == "fedavg_sgd":
        # pre-existing behavior: delegate to the configured base optimizer
        opt = server_update_lib.get_server_update(
            "fedavg_sgd",
            base_opt=opt_lib.get_optimizer(args.server_optimizer, sched))
    else:
        opt = server_update_lib.get_server_update(
            args.server_opt, server_lr=sched, tau=args.server_tau)
    opt_state = opt.init(params)
    start_round = 0
    drift_state = (drift_lib.scaffold_init(params, args.clients_per_round)
                   if args.scaffold else None)
    if args.resume:
        tmpl = {"params": params, "opt": opt_state}
        if args.scaffold:
            tmpl["drift"] = drift_state
        blob, start_round = restore_checkpoint(args.resume, tmpl)
        params, opt_state = blob["params"], blob["opt"]
        if args.scaffold:
            drift_state = blob["drift"]
        print(f"resumed from {args.resume} @ round {start_round}")

    ds, labels = build_dataset(cfg, args)
    apply = make_apply(cfg, de_cfg)

    fused_step = None
    if args.mode == "fused":
        tcfg = TrainConfig(seq_len=args.seq_len,
                           global_batch=args.clients_per_round * args.samples_per_client,
                           samples_per_client=args.samples_per_client,
                           dcco_impl="fused")
        fused_step = jax.jit(steps_lib.make_dcco_train_step(
            cfg, de_cfg, tcfg, opt.opt, num_microbatches=args.micro))

    if cfg.family == "resnet":
        probe_features = make_probe_features(cfg)
        probe_labels = jnp.asarray(labels)

    def evaluate(p):
        if cfg.family != "resnet":
            return float("nan")
        # the in-scan samplers' device copy of the images, not a new one
        z = probe_features(p["tower"], ds.staged()[0]["images"])
        cut = int(len(labels) * 0.7)
        return float(eval_lib.ridge_linear_probe(
            z[:cut], probe_labels[:cut], z[cut:], probe_labels[cut:],
            args.num_classes))

    channel = comm.get_channel(
        args.channel, quant_bits=args.quant_bits,
        quant_kernel=args.quant_kernel, dp_sigma=args.dp_sigma,
        dp_clip=args.dp_clip, dp_delta=args.dp_delta,
        dropout_p=args.dropout_p)
    if args.edges:
        # two-level topology: --channel becomes the client->edge hop
        channel = hierarchy.HierarchicalChannel(
            args.edges, client_channel=channel,
            edge_channel=comm.get_channel(args.edge_channel,
                                          dropout_p=args.dropout_p))
    wire_total = [0.0]

    os.makedirs(args.ckpt_dir, exist_ok=True)
    history = []
    segment_seconds = []
    t0 = time.time()

    if args.mode == "engine":
        chunk = args.chunk_rounds or args.eval_every or 25
        latency = None
        if args.async_k and args.latency_tail > 0:
            latency = latency_lib.LatencyModel(
                "heavytail", horizon=8, tail=args.latency_tail,
                seed=args.seed)
        retrieval_eval = None
        if args.retrieval_eval:
            from repro import retrieval as retrieval_lib
            leaf = "images" if "images" in ds.data else "tokens"
            data_arr = jnp.asarray(ds.data[leaf])
            lab_arr = jnp.asarray(labels)
            nc, nq = args.retrieval_corpus, args.retrieval_queries
            # held-out split: the first nc items are indexed as the
            # corpus, the next nq serve as queries (label-match relevance)

            def embed(p, batch):
                z, _ = dual_encoder.encode(cfg, de_cfg, p, batch)
                return z

            retrieval_eval = retrieval_lib.make_retrieval_eval(
                embed, {leaf: data_arr[:nc]}, lab_arr[:nc],
                {leaf: data_arr[nc:nc + nq]}, lab_arr[nc:nc + nq],
                chunk=min(256, nc),
                index_dtype=(jnp.bfloat16 if args.retrieval_dtype
                             == "bfloat16" else jnp.float32))
        ecfg = round_engine.EngineConfig(
            algorithm="dcco", objective=objective, lam=args.lam,
            client_lr=args.client_lr,
            local_steps=args.local_steps, chunk_rounds=chunk,
            cohort_chunk=args.cohort_chunk,
            stats_kernel=args.stats_kernel, channel=channel,
            compute_dtype=args.compute_dtype,
            server_update=opt, prox_mu=args.fedprox_mu,
            scaffold=args.scaffold, async_k=args.async_k,
            staleness_fn=args.staleness, latency=latency,
            retrieval_eval=retrieval_eval,
            retrieval_every=args.retrieval_every,
            num_clusters=args.clusters,
            cluster_iters=args.cluster_iters)
        if args.cohort_chunk:
            sampler = ds.make_streaming_sampler(args.clients_per_round,
                                                args.cohort_chunk)
        elif args.async_k:
            sampler = ds.make_async_round_sampler(args.clients_per_round,
                                                  latency)
        else:
            sampler = ds.make_round_sampler(args.clients_per_round)
        engine = round_engine.RoundEngine(apply, opt, sampler, ecfg)
        buffer_state = None
        if args.resume and engine._async_real:
            # second pass over the blob: the buffer template needs the
            # built engine (stat shapes come from eval_shape on the
            # sampler), which needs the dataset — both exist only now
            try:
                b, _ = restore_checkpoint(
                    args.resume,
                    {"buffer": engine._init_async_state(params)})
                buffer_state = b["buffer"]
            except KeyError:
                print("resume checkpoint holds no buffer state (written "
                      "by the synchronous engine) — starting the buffered "
                      "run with an empty buffer", flush=True)

        t_seg = [time.time()]

        def on_segment(round_end, carry, m):
            history.extend(float(x) for x in np.asarray(m.loss))
            segment_seconds.append(time.time() - t_seg[0])
            wire_total[0] += float(np.sum(np.asarray(m.wire_bytes)))
            acc = evaluate(carry.params)
            dt = time.time() - t0
            extra = ""
            if args.async_k:
                extra = (f" updates={int(np.sum(np.asarray(m.applied)))}"
                         f"/{m.applied.shape[0]}t")
            if args.retrieval_eval:
                # latest evaluated round in this segment (skipped = NaN)
                r1 = np.asarray(m.retrieval["recall_at_1"])
                live = np.flatnonzero(~np.isnan(r1))
                if live.size:
                    i = live[-1]
                    extra += (
                        f" recall@1={r1[i]:.3f}"
                        f" recall@10="
                        f"{np.asarray(m.retrieval['recall_at_10'])[i]:.3f}"
                        f" mrr={np.asarray(m.retrieval['mrr'])[i]:.3f}")
            print(f"round {round_end:5d} loss={history[-1]:9.4f} "
                  f"enc_std={float(m.encoding_std[-1]):.4f} "
                  f"probe_acc={acc:.3f}{extra} "
                  f"({dt / (round_end - start_round):.2f}s/round)", flush=True)
            t_seg[0] = time.time()

        params, opt_state, _ = engine.run(
            params, opt_state, jax.random.PRNGKey(args.seed),
            args.rounds - start_round, start_round=start_round,
            on_segment=on_segment, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, ckpt_name=args.arch,
            drift_state=drift_state, buffer_state=buffer_state)
        return _report(args, history, evaluate, params, channel,
                       wire_total[0], segment_seconds)

    for r in range(start_round, args.rounds):
        rkey = jax.random.PRNGKey(args.seed * 100003 + r)
        if args.mode == "protocol":
            batch, sizes = ds.round_batch(rkey, args.clients_per_round)
            out = fed_sim.stats_round(
                apply, params, opt_state, opt, batch, sizes,
                objective=objective, client_lr=args.client_lr,
                local_steps=args.local_steps, prox_mu=args.fedprox_mu,
                scaffold_state=drift_state, channel=channel,
                channel_key=jax.random.fold_in(
                    rkey, round_engine._CHANNEL_SALT))
            if args.scaffold:
                params, opt_state, drift_state, m = out
            else:
                params, opt_state, m = out
            if channel is not None:
                channel.finalize_rounds(1)
                wire_total[0] += float(m.wire_bytes)
            loss = float(m.loss)
        else:
            flat, _ = ds.flat_round_batch(rkey, args.clients_per_round)
            leaf = "images" if "images" in ds.data else "tokens"
            batch = {"view1": {leaf: flat["v1"]}, "view2": {leaf: flat["v2"]}}
            params, opt_state, m = fused_step(params, opt_state, batch)
            loss = float(m["loss"])
        history.append(loss)
        if (r + 1) % args.eval_every == 0:
            acc = evaluate(params)
            dt = time.time() - t0
            print(f"round {r + 1:5d} loss={loss:9.4f} probe_acc={acc:.3f} "
                  f"({dt / (r - start_round + 1):.2f}s/round)", flush=True)
        if (r + 1) % args.ckpt_every == 0:
            path = os.path.join(args.ckpt_dir, f"{args.arch}.msgpack")
            blob = {"params": params, "opt": opt_state}
            if args.scaffold:
                blob["drift"] = drift_state
            save_checkpoint(path, blob, r + 1)
    return _report(args, history, evaluate, params, channel, wire_total[0],
                   segment_seconds)


def _report(args, history, evaluate, params, channel=None, wire_bytes=0.0,
            segment_seconds=()):
    probe = evaluate(params)
    out = {"history": history, "probe": probe,
           "segment_seconds": list(segment_seconds)}
    if history:
        print(f"final loss {history[-1]:.4f}; first {history[0]:.4f}; "
              f"probe {probe:.3f}")
    else:
        print(f"no rounds to run (resumed at or past --rounds "
              f"{args.rounds}); probe {probe:.3f}")
        return out
    if channel is not None:
        line = f"channel {channel!r}: uplink {wire_bytes / 1e6:.3f} MB total"
        acct = getattr(channel, "accountant", None)
        if acct is not None:
            line += (f"; DP epsilon={acct.epsilon():.2f} "
                     f"@ delta={acct.delta:g}")
        print(line)
    with open(os.path.join(args.ckpt_dir, "history.json"), "w") as f:
        json.dump(history, f)
    return out


if __name__ == "__main__":
    main()
