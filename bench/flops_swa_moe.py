"""Operations and bytes of the serving work of a decoder whose layers mix
sliding-window and full attention and whose MLPs are routed experts
(Mellum2's block), computed from its shapes alone: the yardstick for the
roofline shares and the model FLOP utilization of such cells.

Shapes come from the configuration file's Hugging Face keys
(``hidden_size``, ``layer_types``, ``num_experts``, ...).  FLOPs count a
multiply-add as two, and only the work the algorithm needs: real prompt
tokens (not their bucket); the top-k experts of each token, never the
rest; the router; causal query-key pairs, clipped to the window on
sliding layers; the logits of the last prompt position.  Bytes are what a
decode step cannot avoid moving: the weights of attention, router and head
once, the weights of the experts its tokens hit once, the live key/value
entries read (``attended_full`` per full layer, ``attended_window`` per
sliding layer) and the new rows written.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class SwaMoeDims:
    d: int              # hidden size
    heads: int
    kv_heads: int
    head_dim: int
    ff: int             # expert width
    experts: int
    top_k: int
    vocab: int
    n_full: int         # full-attention layers held
    n_sliding: int      # sliding-window layers held
    window: int
    weight_bytes: int = 2   # bf16
    kv_bytes: int = 2       # bf16 cache

    @classmethod
    def from_config(cls, c: dict) -> "SwaMoeDims":
        kinds = c["layer_types"][:c["num_hidden_layers"]]
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["moe_intermediate_size"], experts=c["num_experts"],
                   top_k=c["num_experts_per_tok"], vocab=c["vocab_size"],
                   n_full=kinds.count("full_attention"),
                   n_sliding=kinds.count("sliding_attention"),
                   window=c["sliding_window"])

    @property
    def layers(self) -> int:
        return self.n_full + self.n_sliding

    # -- parameters -----------------------------------------------------
    @property
    def attn_params(self) -> int:
        """q, k, v and o projections of one layer."""
        d, hd = self.d, self.head_dim
        return 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd

    @property
    def expert_params(self) -> int:
        """Gate, up and down matrices of one expert."""
        return 3 * self.d * self.ff

    @property
    def dense_layer_params(self) -> int:
        """What every token reads of a layer whatever its routing:
        attention, router and the two RMSNorm scales."""
        return self.attn_params + self.d * self.experts + 2 * self.d

    @property
    def kv_row_bytes(self) -> int:
        """Key and value of one position in one layer."""
        return 2 * self.kv_heads * self.head_dim * self.kv_bytes

    # -- work -------------------------------------------------------------
    def window_pairs(self, n: int) -> int:
        """Query-key pairs of an n-token prompt on a sliding layer:
        position i sees min(i + 1, window) keys."""
        w = self.window
        if n <= w:
            return n * (n + 1) // 2
        return w * (w + 1) // 2 + (n - w) * w

    def _attn_core_flops(self, pairs_full: float, pairs_window: float
                         ) -> float:
        return 4.0 * self.heads * self.head_dim * (
            self.n_full * pairs_full + self.n_sliding * pairs_window)

    def token_flops(self) -> float:
        """Projections, router and the top-k experts of one token over
        every layer held."""
        return 2.0 * self.layers * (self.attn_params + self.d * self.experts
                                    + self.top_k * self.expert_params)

    def prefill_flops(self, prompt_len: int) -> float:
        n = prompt_len
        return (n * self.token_flops()
                + self._attn_core_flops(n * (n + 1) / 2,
                                        self.window_pairs(n))
                + 2.0 * self.d * self.vocab)

    def decode_flops(self, active: int, attended_full: int,
                     attended_window: int) -> float:
        """One decode step of ``active`` sequences that read
        ``attended_full`` entries per full layer and ``attended_window``
        per sliding layer in all (their own token included)."""
        return (active * (self.token_flops() + 2.0 * self.d * self.vocab)
                + self._attn_core_flops(attended_full, attended_window))

    def decode_bytes(self, active: int, attended_full: int,
                     attended_window: int, experts_hit: int) -> float:
        """``experts_hit``: experts with at least one token, summed over
        the layers (the engine's ``moe_experts_hit`` for the step)."""
        weights = (self.layers * self.dense_layer_params
                   + experts_hit * self.expert_params
                   + self.vocab * self.d + active * self.d
                   ) * self.weight_bytes
        kv = (self.n_full * attended_full + self.n_sliding * attended_window
              + self.layers * active) * self.kv_row_bytes
        return float(weights + kv)

    def expert_flops(self, pairs: int) -> float:
        """The grouped matmuls of ``pairs`` routed token-expert pairs."""
        return 2.0 * pairs * self.expert_params

    def expert_bytes(self, pairs: int, experts_hit: int) -> float:
        """The grouped matmuls' traffic: each hit expert's weights once;
        per pair the bf16 rows in (twice for gate and up, once for down)
        and the float32 rows out."""
        per_pair = (2 * self.d * self.weight_bytes + 2 * self.ff * 4
                    + self.ff * self.weight_bytes + self.d * 4)
        return float(experts_hit * self.expert_params * self.weight_bytes
                     + pairs * per_pair)

    @staticmethod
    def roofline_s(flops: float, nbytes: float, peaks: dict
                   ) -> tuple[float, str]:
        """The least time the chip could take, and which bound sets it."""
        compute = flops / peaks["bf16_flops"]
        memory = nbytes / peaks["hbm_bytes_per_s"]
        return (compute, "compute") if compute >= memory else (memory,
                                                               "memory")
