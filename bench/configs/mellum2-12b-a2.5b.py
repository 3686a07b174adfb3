"""mellum2-12b-a2.5b: Mellum2-12B-A2.5B at its published widths, two whole
periods of its layer pattern (6 sliding-window layers, 2 full), served by
``repro.serve.ServeEngine`` in the session-keyed DataX unit
``repro.serve.SessionUnit``, deployed through ``App`` -> ``connect()`` ->
the executor -> the bus.

The benchmark makes the weights: one jitted call from the seed, in
bfloat16, laid out as the program's parameter tree.  The plain reference
makes the same weights from the same seed with the same code, one layer at
a time, and runs the block in float32 under
``default_matmul_precision("highest")``: embedding; per layer RMSNorm,
q/k/v projections, rotary embedding (rotate-half; default frequencies on
sliding layers, YaRN's with cos and sin times its attention factor on full
layers, from ``rope_parameters``), grouped-query softmax attention over
key j ≤ i (full) or i - window < j ≤ i (sliding), in blocks of queries;
output projection; RMSNorm; softmax router, top-k renormalised, and every
expert computed densely for every token, weighted by its gate; final
RMSNorm and the untied head.

Decoding is greedy, so each served token should be the reference's best,
or lie below it by rounding only.  The number compared is the mean of that
gap over every served token of a seeded sample of the window's finished
requests that outgrew the window (their rings wrapped).  The mean, and not
qwen3-14b's widest gap: in bfloat16 a token whose router scores two experts
within rounding of each other takes the other one now and then, which moves
its logits far more than rounding elsewhere does, so the widest gap of a
sound run is set by the rarest such near-tie (PERF.md).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import repro.configs.mellum2_12b  # noqa: F401  (fails where the model is not)
from flops_swa_moe import SwaMoeDims
from loadgen import draw_sizes

_EVENTS = 4096          # requests whose prompts are made up front
_QUERY_BLOCK = 512      # reference positions per block of queries


# ---------------------------------------------------------------------------
# weights, from the seed, shared by the program and the reference
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    import jax
    import jax.numpy as jnp
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(jnp.bfloat16)


def _scale_vec(key, n):
    import jax
    import jax.numpy as jnp
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def init_layer(key, dm: SwaMoeDims) -> dict:
    """One decoder layer's weights in bfloat16, in the program's layout."""
    import jax
    ks = jax.random.split(key, 9)
    d, hd, f, E = dm.d, dm.head_dim, dm.ff, dm.experts
    qd, kvd = dm.heads * hd, dm.kv_heads * hd
    return {
        "ln1": {"scale": _scale_vec(ks[0], d)},
        "attn": {"wq": _normal(ks[1], (d, qd), 1 / math.sqrt(d)),
                 "wk": _normal(ks[2], (d, kvd), 1 / math.sqrt(d)),
                 "wv": _normal(ks[3], (d, kvd), 1 / math.sqrt(d)),
                 "wo": _normal(ks[4], (qd, d), 1 / math.sqrt(qd))},
        "ln2": {"scale": _scale_vec(ks[5], d)},
        "mlp": {"router": _normal(ks[6], (d, E), 1 / math.sqrt(d)),
                "w_gate": _normal(ks[7], (E, d, f), 1 / math.sqrt(d)),
                "w_up": _normal(jax.random.fold_in(ks[7], 1), (E, d, f),
                                1 / math.sqrt(d)),
                "w_down": _normal(ks[8], (E, f, d), 1 / math.sqrt(f))},
    }


def init_embed(key, dm: SwaMoeDims):
    return _normal(key, (dm.vocab, dm.d), 0.02)


def init_unembed(key, dm: SwaMoeDims):
    # 1/sqrt(hidden): logits of about unit spread at every width, so a gap
    # read at a test's small width means what it means at full width
    return _normal(key, (dm.d, dm.vocab), 1 / math.sqrt(dm.d))


def _keys_traced(seed_arr):
    import jax
    base = jax.random.key(seed_arr)
    return {name: jax.random.fold_in(base, i) for i, name in
            enumerate(("embed", "unembed", "layers", "final_norm"))}


def init_params(seed: int, dm: SwaMoeDims) -> dict:
    """Every weight of the served model, made on the device in one jitted
    call, in the program's parameter tree."""
    import jax
    import jax.numpy as jnp

    def make(seed_arr):
        k = _keys_traced(seed_arr)
        layers = jax.lax.map(
            lambda l: init_layer(jax.random.fold_in(k["layers"], l), dm),
            jnp.arange(dm.layers))
        return {"embed": {"table": init_embed(k["embed"], dm),
                          "unembed": init_unembed(k["unembed"], dm)},
                "layers": layers,
                "final_norm": {"scale": _scale_vec(k["final_norm"], dm.d)}}
    return jax.jit(make)(jnp.asarray(seed, jnp.uint32))


def _seed32(seed: int) -> int:
    """The weights' seed: the run's seed folded to 32 bits."""
    return int(seed) % (1 << 32)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def layer_kinds(conf: dict) -> list[str]:
    """"sliding" or "full" for each layer held, from ``layer_types``."""
    kinds = conf["layer_types"][:conf["num_hidden_layers"]]
    return ["sliding" if t == "sliding_attention" else "full" for t in kinds]


def rope_tables(conf: dict, kind: str, positions: np.ndarray):
    """cos and sin [S, head_dim // 2] of one layer kind, in float64 then
    float32, from ``rope_parameters``: the default frequencies
    θ^(-2i/d), or YaRN's NTK-by-parts blend (arXiv:2309.00071 §3.2, the
    ramp's ends rounded outward to whole dimensions as Hugging Face does)
    with cos and sin times its attention factor."""
    rp = conf["rope_parameters"][f"{kind}_attention"]
    d, theta = conf["head_dim"], float(rp["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, d, 2) / d)
    scale = 1.0
    if rp["rope_type"] == "yarn":
        orig, factor = rp["original_max_position_embeddings"], rp["factor"]

        def dim_of(rotations):
            return (d * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        lo = max(math.floor(dim_of(rp["beta_fast"])), 0)
        hi = min(math.ceil(dim_of(rp["beta_slow"])), d - 1)
        hi = hi + 0.001 if hi == lo else hi
        ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
        scale = rp["attention_factor"]
    ang = positions[:, None].astype(np.float64) * freqs[None, :]
    return ((np.cos(ang) * scale).astype(np.float32),
            (np.sin(ang) * scale).astype(np.float32))


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
                ) * scale


def ref_layer(x, lp, cos, sin, conf: dict, kind: str):
    """One decoder layer in float32.  x: [S, d]; cos, sin: [S, Dh / 2]."""
    import jax
    import jax.numpy as jnp
    dm = SwaMoeDims.from_config(conf)
    eps = conf["rms_norm_eps"]
    H, KH, Dh, E = dm.heads, dm.kv_heads, dm.head_dim, dm.experts
    S = x.shape[0]
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    attn = f32(lp["attn"])
    h = _rms(x, lp["ln1"]["scale"].astype(jnp.float32), eps)
    q = (h @ attn["wq"]).reshape(S, H, Dh)
    k = (h @ attn["wk"]).reshape(S, KH, Dh)
    v = (h @ attn["wv"]).reshape(S, KH, Dh)

    def rope(t):
        half = Dh // 2
        t1, t2 = t[..., :half], t[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)
    q, k = rope(q), rope(k)
    k = jnp.repeat(k, H // KH, axis=1)          # head h reads kv head h // G
    v = jnp.repeat(v, H // KH, axis=1)
    nb = S // _QUERY_BLOCK
    window = conf["sliding_window"] if kind == "sliding" else S

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * _QUERY_BLOCK, _QUERY_BLOCK)
        i = b * _QUERY_BLOCK + jnp.arange(_QUERY_BLOCK)[:, None]
        j = jnp.arange(S)[None, :]
        seen = (j <= i) & (j > i - window)
        a = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(Dh)
        a = jnp.where(seen[None], a, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(a, axis=-1), v)
    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * Dh)
    x = x + o @ attn["wo"]

    h = _rms(x, lp["ln2"]["scale"].astype(jnp.float32), eps)
    probs = jax.nn.softmax(h @ lp["mlp"]["router"].astype(jnp.float32),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    gates = jnp.zeros((S, E), jnp.float32).at[
        jnp.arange(S)[:, None], idx].set(top)         # zero off the top-k

    def expert(out, inputs):
        g, wg, wu, wd = inputs
        y = jax.nn.silu(h @ wg.astype(jnp.float32)) * (
            h @ wu.astype(jnp.float32))
        return out + g[:, None] * (y @ wd.astype(jnp.float32)), None
    mlp = lp["mlp"]
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (gates.T, mlp["w_gate"], mlp["w_up"],
                           mlp["w_down"]))
    return x + out


def reference_gaps(seed: int, conf: dict, seqs: list, targets: list) -> list:
    """Float32 reference over each token sequence in ``seqs`` (padded to a
    whole number of query blocks; causal attention leaves real positions
    untouched by the padding).  ``targets[i][p]`` is the token served after
    position ``p``.  Returns, per sequence, how far the target's logit lies
    below the reference's best at each position.  Weights are made layer by
    layer from the seed, so one layer's weights are on the device at a
    time, and logits never leave it."""
    import jax
    import jax.numpy as jnp
    dm = SwaMoeDims.from_config(conf)
    eps = conf["rms_norm_eps"]
    seed_arr = jnp.asarray(_seed32(seed), jnp.uint32)
    length = -(-max(len(s) for s in seqs) // _QUERY_BLOCK) * _QUERY_BLOCK
    toks = np.zeros((len(seqs), length), np.int32)
    tgt = np.zeros((len(seqs), length), np.int32)
    for i, (s, t) in enumerate(zip(seqs, targets)):
        toks[i, :len(s)] = s
        tgt[i, :len(t)] = t
    tables = {kind: tuple(jnp.asarray(a) for a in
                          rope_tables(conf, kind, np.arange(length)))
              for kind in ("sliding", "full")}
    kinds = layer_kinds(conf)

    @jax.jit
    def embed(s, t):
        return init_embed(_keys_traced(s)["embed"], dm)[t].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=3)
    def layer(s, l, xs, kind):
        lp = init_layer(jax.random.fold_in(_keys_traced(s)["layers"], l), dm)
        cos, sin = tables[kind]
        return jax.lax.map(lambda xi: ref_layer(xi, lp, cos, sin, conf,
                                                kind), xs)

    @jax.jit
    def head(s, xs, t):
        k = _keys_traced(s)
        norm = _scale_vec(k["final_norm"], dm.d).astype(jnp.float32)
        w = init_unembed(k["unembed"], dm).astype(jnp.float32)

        def one(args):
            x, ti = args
            xb = x.reshape(-1, _QUERY_BLOCK, dm.d)
            tb = ti.reshape(-1, _QUERY_BLOCK)

            def block(a):
                ref = _rms(a[0], norm, eps) @ w
                return ref.max(axis=1) - ref[jnp.arange(_QUERY_BLOCK), a[1]]
            return jax.lax.map(block, (xb, tb)).reshape(-1)
        return jax.lax.map(one, (xs, t))

    with jax.default_matmul_precision("highest"):
        x = embed(seed_arr, jnp.asarray(toks))
        for l, kind in enumerate(kinds):
            x = layer(seed_arr, jnp.asarray(l, jnp.uint32), x, kind)
        gaps = np.asarray(head(seed_arr, x, jnp.asarray(tgt)))
    return [gaps[i, :len(s)] for i, s in enumerate(seqs)]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

class Cell:
    output = "answers"

    def __init__(self, conf: dict, mix: dict, seed: int, chips: int):
        self.conf = conf
        self.mix = mix
        self.seed = seed
        self.dims = SwaMoeDims.from_config(conf)
        sizes = draw_sizes(mix, seed, _EVENTS)
        self.prompt_len = sizes["prompt_len"]
        self.answer_len = sizes["answer_len"]
        rng = np.random.default_rng([seed, 4])
        ids = rng.integers(1, self.dims.vocab, int(self.prompt_len.sum()),
                           dtype=np.int32)
        self.prompts = np.split(ids, np.cumsum(self.prompt_len)[:-1])
        self.unit = None
        self.final_counters: dict = {}
        self._served: dict[int, np.ndarray] = {}
        import threading
        self._lock = threading.Lock()

    def payload(self, i: int, client: int) -> dict:
        return {"request_id": f"r{i}", "session": f"s{client}",
                "prompt": self.prompts[i], "max_new": int(self.answer_len[i])}

    # -- deployment --------------------------------------------------------
    def model(self):
        """The program's model and run configuration, from the file."""
        from repro.configs.base import (ModelConfig, MoEConfig, RunConfig,
                                        YarnConfig)
        from repro.configs.mellum2_12b import ARCH_ID
        c, s = self.conf, self.conf["serving"]
        kinds = layer_kinds(c)
        period = next(p for p in range(1, len(kinds) + 1)
                      if len(kinds) % p == 0
                      and kinds == kinds[:p] * (len(kinds) // p))
        rp = c["rope_parameters"]
        full, sliding = rp["full_attention"], rp["sliding_attention"]
        if full["rope_theta"] != sliding["rope_theta"]:
            raise ValueError("one rope_theta for both layer kinds")
        if not c["norm_topk_prob"]:
            raise ValueError("the program renormalises the top-k gates")
        yarn = None
        if full["rope_type"] == "yarn":
            yarn = YarnConfig(
                factor=float(full["factor"]),
                original_max_position=full["original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full["attention_factor"]))
        cfg = ModelConfig(
            name=ARCH_ID, family="moe",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["moe_intermediate_size"], vocab=c["vocab_size"],
            head_dim=c["head_dim"], qk_norm=False,
            rope_theta=float(full["rope_theta"]), norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], act="silu",
            moe=MoEConfig(num_experts=c["num_experts"],
                          top_k=c["num_experts_per_tok"]),
            layer_pattern=tuple(kinds[:period]),
            sliding_window=c["sliding_window"], yarn=yarn,
            param_dtype=c["torch_dtype"], activation_dtype=c["torch_dtype"])
        run = RunConfig(attention_impl=s["attention_impl"],
                        attention_chunk=s["attention_chunk"], remat="none")
        return cfg, run

    @property
    def steps(self) -> list:
        return self.unit.steps if self.unit is not None else []

    @property
    def engine(self):
        return self.unit.engine if self.unit is not None else None

    def warm_lengths(self) -> list[int]:
        """Prompt lengths that reach every prefill shape of the mix: the
        shortest, each power of two above it, and the longest."""
        lo, hi = int(self.prompt_len.min()), int(self.prompt_len.max())
        out, b = {lo, hi}, 1
        while b < hi:
            if b > lo:
                out.add(b)
            b *= 2
        return sorted(out)

    def deploy(self, op, load):
        from repro.serve import ServeEngine, SessionUnit

        cfg, run = self.model()
        s = self.conf["serving"]

        def make_engine(db):
            params = init_params(_seed32(self.seed), self.dims)
            return ServeEngine(cfg, run, params, n_slots=s["kv_slots"],
                               max_seq=s["max_seq"], db=db, eos_id=s["eos"])
        self.unit = SessionUnit(make_engine, warm=self._warm,
                                output=self.output,
                                app_name="bench-mellum2-12b-a2.5b")
        return self.unit.deploy(op, lambda ctx: load.events(
            lambda: ctx.running))

    def _warm(self, engine) -> None:
        """Compile every prefill shape the mix reaches, its slot inserts,
        and the decode step, before any traffic."""
        rng = np.random.default_rng(0)
        for n in self.warm_lengths():
            engine.submit(f"warm-{n}", [int(t) for t in rng.integers(
                1, self.dims.vocab, n)], max_new_tokens=2)
            engine.run_until_idle()
        engine.batcher.completed.clear()
        for k in engine.metrics:
            engine.metrics[k] = 0

    # -- results -------------------------------------------------------------
    def collect(self, payload: dict, t: float, load) -> None:
        i = int(payload["request_id"][1:])
        if payload["kind"] == "first":
            load.first(i, t)
        else:
            with self._lock:
                self._served[i] = np.asarray(payload["tokens"])
            load.done(i, t)

    def _owed(self, load, window) -> list[int]:
        """Requests due before the window's end: the run's schedule.  One
        due later was sent only while the harness was still closing the
        window (stopping the profiler takes seconds), outside the run."""
        due = list(load.due)
        return [i for i, t in enumerate(due) if t < window[1]]

    def settled(self, load, window) -> bool:
        owed = self._owed(load, window)
        with self._lock:
            return all(i in self._served for i in owed)

    def counters(self) -> dict:
        return dict(self.engine.metrics)

    def event_info(self, n: int) -> dict:
        tokens = np.zeros(n, np.int64)
        with self._lock:
            for i, toks in self._served.items():
                if i < n:
                    tokens[i] = len(toks)
        return {"tokens": tokens}

    def close(self) -> None:
        if self.engine is not None:
            self.final_counters = dict(self.engine.metrics)
            self.unit.engine = None

    def ring(self) -> int:
        return min(self.conf["sliding_window"], self.conf["serving"]["max_seq"])

    def sample(self, load, window) -> list[int]:
        """Finished requests due in the window whose prompt and answer
        outgrew the ring (it wrapped), drawn from the seed, the longest
        among them first."""
        lo, hi = window
        done = [i for i in sorted(self._served)
                if i < len(load.due) and lo <= load.due[i] < hi
                and self.prompt_len[i] + len(self._served[i]) > self.ring()]
        if not done:
            return []
        k = self.conf["reference"]["sample_requests"]
        longest = max(done, key=lambda i: self.prompt_len[i]
                      + len(self._served[i]))
        rest = [i for i in done if i != longest]
        rng = np.random.default_rng([self.seed, 5])
        picked = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
        return [longest] + [rest[j] for j in sorted(picked)]

    def gaps(self, picked: list[int]) -> np.ndarray:
        """How far each served token of the picked requests lies below the
        reference's best."""
        seqs, targets = [], []
        for i in picked:
            prompt, served = self.prompts[i], self._served[i]
            seqs.append(np.concatenate([prompt, served[:-1]]))
            t = np.zeros(len(prompt) + len(served) - 1, np.int32)
            t[len(prompt) - 1:] = served
            targets.append(t)
        out = reference_gaps(self.seed, self.conf, seqs, targets)
        return np.concatenate([g[len(self.prompts[i]) - 1:]
                               for g, i in zip(out, picked)])

    def check(self, load, window) -> list:
        owed = self._owed(load, window)
        with self._lock:
            unanswered = sum(1 for i in owed if i not in self._served)
            wrong_length = sum(1 for i, t in self._served.items()
                               if len(t) != self.answer_len[i])
        picked = self.sample(load, window)
        gaps = self.gaps(picked) if picked else np.zeros(0)
        # nothing compared is no evidence: it reads as a failure
        gap = float(gaps.mean()) if len(gaps) else 1e9
        self.compared = {"requests": len(picked), "positions": int(sum(
            self.prompt_len[i] + len(self._served[i]) for i in picked)),
            "tokens": len(gaps),
            "gap_max": float(gaps.max()) if len(gaps) else None}
        # a missing counter (a program without them) reads as a failure too
        dropped = self.final_counters.get("moe_dropped", 1e9)
        return [("logit_gap_mean", gap, LOGIT_GAP_LIMIT),
                ("wrong_length", wrong_length, 0),
                ("unanswered", unanswered, 0),
                ("moe_dropped", dropped, 0)]


# The mean gap of the served tokens' logits below the reference's best, set
# from readings on a TPU v5e (PERF.md): sound runs read 0.0039-0.0087 over
# nine seeds, the control (the served weights rounded to float8) 0.046 and
# 0.066.  The limit lies between them with over twice room on each side.
LOGIT_GAP_LIMIT = 0.02
