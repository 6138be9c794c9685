"""A configuration's code found by name: the ResNet cells give what they
gave before their code moved into ``bench/families/resnet.py`` and
``bench/protocol.py`` (values pinned from the tree before the move); a
family that lives in new files only, on images or on integer tokens, runs
end to end; a cohort of 100 clients, no multiple of 16, runs too."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import run
from test_bench_run import tiny

SEED = 2**31 + 11


def digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# at ``tiny`` sizes, seed 2**31 + 11; FLOPs of a sample at full size
PARENT = {
    "r14-k256-n2": {
        "inputs": "97a426ece9d149b1", "params": "696253337a97fe73",
        "flops_full": 5082710016.0, "flops_tiny": 26566656.0,
        "losses": [157.65985107421875, 151.41513061523438],
        "change": "2e2ede8cb23db3c4", "m": "c5c03937b70f16ac"},
    "r18-k256-q1to6-stream": {
        "inputs": "95d2651be7f24b80", "params": "696253337a97fe73",
        "flops_full": 6696468480.0, "flops_tiny": 26566656.0,
        "losses": [113.62716674804688, 95.00182342529297],
        "change": "48ad964fbdbb1690", "m": "072720ef50ffb66d"},
}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_resnet_cell_equals_parent(cell):
    want = PARENT[cell]
    full, spec = run.load_cell(cell), tiny(cell)
    data, _ = run.make_inputs(spec, SEED)
    assert digest(data.leaves["images"], data.labels, data.client_index,
                  data.client_sizes) == want["inputs"]
    assert digest(*jax.tree.leaves(run.make_params(jax, spec, SEED))) \
        == want["params"]
    assert run.family(full).flops_per_sample(full["model"], 1) \
        == want["flops_full"]
    assert run.family(spec).flops_per_sample(spec["model"], 1) \
        == want["flops_tiny"]
    ref = run.run_reference(jax, spec, data, SEED, 2)
    assert ref["losses"] == want["losses"]
    assert digest(*jax.tree.leaves(ref["change"])) == want["change"]
    assert digest(*jax.tree.leaves(ref["m"])) == want["m"]


# -- a family in new files only --------------------------------------------------

TOY_FAMILY = '''"""A toy family: one-channel 8x8 images of uniform noise around a class
level, drawn here from the seed."""
import numpy as np

from bench import traffic
from bench.families import resnet


def model_config(model):
    return resnet.model_config(model)


def make_inputs(traffic_spec, model, seed):
    d = traffic_spec["dataset"]
    s = model["image_size"]
    rng = np.random.Generator(np.random.PCG64(
        traffic.seed_sequence(seed, "toy")))
    labels = rng.integers(0, d["num_classes"], d["num_samples"])
    images = (rng.random((d["num_samples"], s * s), np.float32) * 0.5
              + labels[:, None].astype(np.float32) / (2 * d["num_classes"]))
    return ({"images": images}, {"images": (s, s, 1)},
            labels.astype(np.int32))


def flops_per_sample(model, local_steps):
    return 1e6 * local_steps
'''

TOY_REFERENCE = '''"""The toy family's reference: the ResNet encoder, views and
initialisation."""
from bench.reference import encode, init_params, views  # noqa: F401
'''


TOY_CONFIG = {
    "name": "toy8", "family": "toy", "source": "a test",
    "reference": "bench/toy_reference.py", "num_layers": 2,
    "resnet_stages": [1], "resnet_channels": [8],
    "resnet_groups": 4, "resnet_in_channels": 1, "image_size": 8,
    "proj_dims": [16, 16], "lambda_cco": 20.0, "dtype": "float32",
    "compute_dtype": "float32", "reduced": []}


def _toy_root(tmp_path, family=TOY_FAMILY, reference=TOY_REFERENCE,
              config=TOY_CONFIG):
    files = {
        "bench/families/toy.py": family,
        "bench/toy_reference.py": reference,
        "bench/configs/toy8.json": config,
        "bench/traffic/toy-n2.json": {
            "dataset": {"num_samples": 160, "num_classes": 4},
            "partition": {"strategy": "dirichlet", "samples_per_client": 2,
                          "alpha": 0.0},
            "clients_per_round": 8, "local_steps": 1, "client_lr": 1.0,
            "server": {"optimizer": "adam", "lr": 0.002, "b1": 0.9,
                       "b2": 0.999, "eps": 1e-08, "cosine_rounds": 1000},
            "channel": "none"},
        "bench/workloads/toy8-n2.json": {
            "config": "toy8", "traffic": "toy-n2", "chips": 1,
            "why": "a family from new files", "chunk_rounds": 2,
            "cohort_chunk": 0, "stats_kernel": "off",
            "limits": {"loss_gap": 0.01, "change_gap": 0.1,
                       "grad_gap_median": 0.05}},
        "BENCHMARK.json": {
            "workloads": [{"name": "toy8-n2", "config": "toy8",
                           "traffic": "toy-n2", "chips": 1,
                           "why": "a family from new files"}],
            "end_to_end": [
                {"name": "samples_per_s", "unit": "samples/s",
                 "better": "higher", "bound": 0.02, "source": "host_clock"},
                {"name": "setup_s", "unit": "s", "better": "lower",
                 "bound": 0.25, "source": "host_clock"}],
            "per_layer": []},
    }
    for path, body in files.items():
        p = tmp_path / path
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body if isinstance(body, str) else json.dumps(body))
    return str(tmp_path)


def _run_toy(tmp_path, **files):
    before = sorted(os.listdir(os.path.join(run.BENCH_DIR, "families")))
    spec = run.load_cell("toy8-n2", root=_toy_root(tmp_path, **files))
    assert run.family(spec).__file__.startswith(str(tmp_path))
    assert run.reference(spec).__file__.startswith(str(tmp_path))
    res = run.run_cell(spec, SEED, 0.5, False, require_tpu=False,
                       log=lambda *a: None)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}
    assert sorted(os.listdir(os.path.join(run.BENCH_DIR, "families"))) \
        == before
    return spec, res


def test_family_from_new_files_runs_correct(tmp_path):
    _run_toy(tmp_path)


# -- a token family: another leaf, another encoder, other views ------------------

TOKEN_FAMILY = '''"""A toy token family: sequences of integer tokens, each a class's
prototype sequence with a quarter of its positions redrawn, encoded by the
program's dense transformer tower."""
import numpy as np

from bench import traffic


def model_config(model):
    from repro.configs.base import DualEncoderConfig, ModelConfig
    cfg = ModelConfig(
        name=model["name"], family="dense", source=model["source"],
        num_layers=model["num_layers"], d_model=model["d_model"],
        num_heads=model["num_heads"], num_kv_heads=model["num_heads"],
        d_ff=model["d_ff"], vocab_size=model["vocab_size"],
        dtype=model["dtype"], attn_impl="naive")
    de_cfg = DualEncoderConfig(proj_dims=tuple(model["proj_dims"]),
                               lambda_cco=model["lambda_cco"],
                               shared_towers=True)
    return cfg, de_cfg


def make_inputs(traffic_spec, model, seed):
    d = traffic_spec["dataset"]
    n, s, v = d["num_samples"], model["seq_len"], model["vocab_size"]
    rng = np.random.Generator(np.random.PCG64(
        traffic.seed_sequence(seed, "toy-tokens")))
    protos = rng.integers(1, v, (d["num_classes"], s))
    labels = rng.integers(0, d["num_classes"], n)
    redrawn = rng.integers(1, v, (n, s))
    keep = rng.random((n, s)) < 0.75
    tokens = np.where(keep, protos[labels], redrawn).astype(np.int32)
    return {"tokens": tokens}, {"tokens": (s,)}, labels.astype(np.int32)


def flops_per_sample(model, local_steps):
    return 1e6 * local_steps
'''

TOKEN_REFERENCE = '''"""The token family's reference, written from the description of the
encoder: token embedding, pre-norm blocks of causal self-attention with
rotary positions (each head's halves rotated) and a SwiGLU MLP, a final
RMSNorm, the mean over positions, then the MLP head; the views mask 15%
of the tokens to 0 and, half the time, roll the sequence by up to a
quarter of its length."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.protocol import lowp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _w(key, d_in, d_out):
    w = jax.random.truncated_normal(key, -2, 2, (d_in, d_out), F32)
    return {"w": w / np.sqrt(d_in)}


def _block(key, d, f):
    k = jax.random.split(key, 7)
    return {"ln1": {"scale": jnp.ones((d,), F32)},
            "ln2": {"scale": jnp.ones((d,), F32)},
            "attn": {"wq": _w(k[0], d, d), "wk": _w(k[1], d, d),
                     "wv": _w(k[2], d, d), "wo": _w(k[3], d, d)},
            "ffn": {"gate": _w(k[4], d, f), "up": _w(k[5], d, f),
                    "down": _w(k[6], f, d)}}


def init_params(key, model):
    d = model["d_model"]
    k_emb, k_blocks, k_proj = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: {"b0": _block(k, d, model["d_ff"])})(
        jax.random.split(k_blocks, model["num_layers"]))
    dims = (d,) + tuple(model["proj_dims"])
    head = [dict(_w(k, dims[i], dims[i + 1]),
                 b=jnp.zeros((dims[i + 1],), F32))
            for i, k in enumerate(jax.random.split(k_proj, len(dims) - 1))]
    table = jax.random.normal(k_emb, (model["vocab_size"], d), F32)
    return {"tower": {"embed": {"table": table}, "layers": blocks,
                      "final_norm": {"scale": jnp.ones((d,), F32)}},
            "proj": {"layers": head}}


def _dot(x, w, dt):
    return jnp.einsum("...i,io->...o", lowp(x, dt), lowp(w, dt),
                      precision=HI)


def _norm(p, x):
    rms = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    return x * rms * p["scale"]


def _rotate(x):
    """(B, S, H, Dh): the pair (i, i + Dh/2) of each head turned by
    position / 10000 ** (2i / Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / 10000.0 ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    angles = (np.arange(s, dtype=np.float32)[:, None] * freqs)[None, :, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, x, heads, dt):
    b, s, d = x.shape
    q, k, v = (_dot(x, p[n]["w"], dt).reshape(b, s, heads, d // heads)
               for n in ("wq", "wk", "wv"))
    q, k = _rotate(q), _rotate(k)
    scores = jnp.einsum("bqhd,bkhd->bhqk", lowp(q, dt), lowp(k, dt),
                        precision=HI) / np.sqrt(d // heads)
    causal = np.tril(np.ones((s, s), bool))
    a = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", lowp(a, dt), lowp(v, dt),
                     precision=HI)
    return _dot(out.reshape(b, s, d), p["wo"]["w"], dt)


def encode(model, params, tokens, dt=None):
    t = params["tower"]
    x = t["embed"]["table"][tokens]
    for i in range(model["num_layers"]):
        p = jax.tree.map(lambda a: a[i], t["layers"])["b0"]
        x = x + _attention(p["attn"], _norm(p["ln1"], x),
                           model["num_heads"], dt)
        h, f = _norm(p["ln2"], x), p["ffn"]
        x = x + _dot(jax.nn.silu(_dot(h, f["gate"]["w"], dt))
                     * _dot(h, f["up"]["w"], dt), f["down"]["w"], dt)
    z = _norm(t["final_norm"], x).mean(axis=1)
    head = params["proj"]["layers"]
    for i, p in enumerate(head):
        z = _dot(z, p["w"], dt) + p["b"]
        if i < len(head) - 1:
            z = jax.nn.relu(z)
    return z


def _view(key, tokens):
    k_mask, k_roll, k_shift, _ = jax.random.split(key, 4)
    s = tokens.shape[-1]
    masked = jnp.where(jax.random.bernoulli(k_mask, 0.15, tokens.shape),
                       0, tokens)
    shift = jax.random.randint(k_shift, (), 0, max(1, int(s * 0.25)))
    return jnp.where(jax.random.bernoulli(k_roll, 0.5),
                     jnp.roll(masked, shift, axis=-1), masked)


def views(key, sample):
    k1, k2 = jax.random.split(key)
    return _view(k1, sample["tokens"]), _view(k2, sample["tokens"])
'''

TOKEN_CONFIG = {
    "name": "toy8", "family": "toy", "source": "a test",
    "reference": "bench/toy_reference.py", "num_layers": 2,
    "d_model": 16, "num_heads": 2, "d_ff": 32, "vocab_size": 32,
    "seq_len": 16, "proj_dims": [16, 16], "lambda_cco": 20.0,
    "dtype": "float32", "compute_dtype": "float32", "reduced": []}


def test_token_family_from_new_files_runs_correct(tmp_path):
    """Integer ``tokens``, the program's transformer tower and its token
    views, each side written anew: the harness takes them as new files."""
    spec, res = _run_toy(tmp_path, family=TOKEN_FAMILY,
                         reference=TOKEN_REFERENCE, config=TOKEN_CONFIG)
    data, _ = run.make_inputs(spec, SEED)
    assert set(data.leaves) == {"tokens"}
    assert data.leaves["tokens"].dtype == np.int32
    # the reference follows the program closely, not by the limits' room
    assert res["checks"]["loss_gap"]["value"] < 1e-4, res["checks"]


# -- a cohort of 100 clients --------------------------------------------------

def test_cohort_of_100_clients_is_correct():
    spec = tiny("r14-k256-n2")
    spec["traffic"]["clients_per_round"] = 100
    res = run.run_cell(spec, SEED, 0.0, False, require_tpu=False,
                       window=False, log=lambda *a: None)
    assert res["correct"] is True, res["checks"]


