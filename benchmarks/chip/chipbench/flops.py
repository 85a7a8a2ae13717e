"""Operations and bytes the work of a serving step needs, from its shapes.

Counted from the tokens a step really computes (its spans), never from
the padded bucket.  A span covers positions ``[start, end)`` of one
sequence; a token at position ``p`` attends to ``p + 1`` keys (causal).
A multiply-add counts as two operations.

* Model: per token, the matmuls of every layer (q, k, v, o, and the
  three of the gated MLP) and attention over its real context (scores
  and the weighted sum of values); the output head only for the rows
  that are sampled.  Norms, RoPE, softmax and sampling are left out
  (a fraction of a percent at these widths).
* Paged-attention kernel, per call (one layer of one step): the same
  attention operations, and the least bytes any kernel must move: each
  live page of each sequence's K and V read once (pages covering
  ``[0, end)``), q read and o written once per token.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

# (start, end, sampled) of one sequence's span in a step
Span = Tuple[int, int, bool]


@dataclass(frozen=True)
class Model:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    page_size: int
    itemsize: int                  # bytes of a served K/V/q element

    @classmethod
    def of(cls, config: dict) -> "Model":
        c = config
        h = c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=h, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // h,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   page_size=c["serve"]["page_size"],
                   itemsize={"bfloat16": 2, "float32": 4}[
                       c["serve"]["dtype"]])

    @property
    def matmul_flops_per_token(self) -> int:
        """Every weight matmul of every layer, for one token."""
        d, q = self.d_model, self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        per_layer = d * q + 2 * d * kv + q * d + 3 * d * self.d_ff
        return 2 * per_layer * self.n_layers

    def attn_flops(self, spans: Iterable[Span]) -> int:
        """Scores and weighted values over the real causal context, one
        layer: 4 * context * heads * head_dim per token."""
        ctx = sum((e * (e + 1) - s * (s + 1)) // 2 for s, e, _ in spans)
        return 4 * ctx * self.n_heads * self.head_dim

    def step_flops(self, spans: Iterable[Span]) -> int:
        spans = list(spans)
        tokens = sum(e - s for s, e, _ in spans)
        sampled = sum(1 for *_, smp in spans if smp)
        return (tokens * self.matmul_flops_per_token
                + self.n_layers * self.attn_flops(spans)
                + sampled * 2 * self.d_model * self.vocab)

    def kernel_bytes(self, spans: Iterable[Span]) -> int:
        """Least bytes of one paged-attention call (one layer)."""
        ps = self.page_size
        page = ps * self.n_kv_heads * self.head_dim * self.itemsize
        pages = 0
        tokens = 0
        for s, e, _ in spans:
            pages += -(-e // ps)
            tokens += e - s
        qo = 2 * tokens * self.n_heads * self.head_dim * self.itemsize
        return 2 * pages * page + qo
