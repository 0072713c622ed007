"""The program's ``ModelConfig`` from a configuration file as the benchmark
holds it (``bench/configs/<name>.json``): the keys of the model's published
``config.json``, as the catalog rows name them, and the benchmark's own
metadata (``name``, ``source``, ``reference``, ``precision``, ``reduced``,
``assumed``, ``departures``, ``deployment``).

It maps the keys of every family the program runs: GQA attention with an
optional sliding window and a local:global interleave, top-k experts on
every ``n``-th layer, Mamba-2 (SSD) mixers, and hybrids of attention and
Mamba-2 layers. The program places a layer of a kind that recurs every
``n`` layers at ``idx % n == n - 1``, so a published offset maps only where
it is ``n - 1``.

A key that it does not know, or whose value the program cannot run as
published, is refused with ``Refused``, which names the key: the program
would otherwise run another model under the configuration's name. A key
that the file leaves out computes what its ``model_type``'s published
implementation does without it (``DEFAULTS``; a file without a
``model_type`` is read as a Llama-layout model), and is refused where that
is not what the program computes. A ``model_type`` whose published defaults
are not tabled here is refused: the reader could not tell what the keys it
leaves out compute.

``harness.program_config`` reads a file with this reader unless the file
names another under ``reader``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

META = frozenset({"name", "source", "reference", "precision", "reduced",
                  "assumed", "departures", "deployment", "reader"})

# Keys that leave what a run computes as it is: a label, the longest
# context the model allows, switches of the published implementation.
INERT = frozenset({"model_type", "max_position_embeddings",
                   "num_logits_to_keep", "use_mamba_kernels", "use_cache"})

# Keys the program has no option for, taken only at the value it computes.
FIXED: Dict[str, Any] = {
    "hidden_act": "silu", "mamba_hidden_act": "silu",
    "partial_rotary_factor": 1.0, "rope_scaling": None,
    "use_qkv_bias": False, "attention_bias": False, "mlp_bias": False,
    "norm_type": "rmsnorm", "norm_topk_prob": True,
    "mamba_conv_bias": True, "use_conv_bias": True,
    "mamba_proj_bias": False, "use_bias": False,
    "mamba_n_groups": 1, "n_groups": 1, "qk_norm": False,
    "use_qk_norm": False,
}

# What each ``model_type``'s published implementation computes where its
# config leaves a FIXED key out; for a key it does not have, what its code
# does without one (OLMoE always normalises q and k; Jamba's router never
# renormalises its top-k gates).
_LLAMA = {"hidden_act": "silu", "norm_type": "rmsnorm",
          "partial_rotary_factor": 1.0, "rope_scaling": None,
          "use_qkv_bias": False, "attention_bias": False, "qk_norm": False,
          "mlp_bias": False}
DEFAULTS: Dict[str, Dict[str, Any]] = {
    "llama": _LLAMA,
    "olmoe": {**_LLAMA, "qk_norm": True, "norm_topk_prob": False},
    "jamba": {**_LLAMA, "norm_topk_prob": False, "mamba_conv_bias": True,
              "mamba_proj_bias": False, "mamba_n_groups": 1},
    "mamba2": {"hidden_act": "silu", "norm_type": "rmsnorm",
               "use_conv_bias": True, "use_bias": False, "n_groups": 1},
}

# The FIXED keys that bear on a model, by what it holds. The keys of one
# tuple are one setting under the names different configs give it.
_ALWAYS = (("hidden_act",), ("norm_type",))
_ATTENTION = (("partial_rotary_factor",), ("rope_scaling",),
              ("use_qkv_bias",), ("attention_bias",),
              ("qk_norm", "use_qk_norm"))
_FFN = (("mlp_bias",),)
_EXPERTS = (("norm_topk_prob",),)
_MIXER = (("mamba_conv_bias", "use_conv_bias"),
          ("mamba_proj_bias", "use_bias"), ("mamba_n_groups", "n_groups"))

_REQUIRED = object()


class Refused(ValueError):
    """A configuration key the program cannot run as published."""

    def __init__(self, config: str, key: str, why: str):
        super().__init__(f"configuration {config!r}: key {key!r} {why}")
        self.key = key


class _Reader:
    """Reads keys of one file and remembers which it used."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.name = str(cfg.get("name", "?"))
        self.used = set(META) | INERT

    def refuse(self, key: str, why: str):
        raise Refused(self.name, key, why)

    def given(self, *keys: str) -> Optional[str]:
        """The one of ``keys`` that the file gives, if any."""
        found = [k for k in keys if k in self.cfg]
        if len(found) > 1:
            self.refuse(found[1], f"gives again what {found[0]!r} gives")
        return found[0] if found else None

    def get(self, *keys: str, default: Any = _REQUIRED) -> Any:
        """The value of the one of ``keys`` that the file gives."""
        key = self.given(*keys)
        if key is not None:
            self.used.add(key)
            return self.cfg[key]
        if default is _REQUIRED:
            self.refuse(keys[0], "is required and not given")
        return default

    def has(self, *keys: str) -> bool:
        return any(k in self.cfg for k in keys)

    def every(self, period_key: str, offset_key: str) -> int:
        """A layer kind's period, where its offset is the program's."""
        n = int(self.get(period_key))
        off = int(self.get(offset_key))
        if n < 1 or off != n - 1:
            self.refuse(offset_key, f"is {off}: the program places a layer "
                        f"of period {n} at idx % {n} == {n - 1}")
        return n

    def check_rest(self, settings) -> None:
        """Every FIXED key given at the program's value, every setting of
        ``settings`` left out where the published default is the program's,
        and no key unread."""
        for k, want in FIXED.items():
            if k in self.cfg:
                self.used.add(k)
                if self.cfg[k] != want:
                    self.refuse(k, f"is {self.cfg[k]!r}; the program "
                                f"computes only {want!r}")
        kind = self.cfg.get("model_type", "llama")
        published = DEFAULTS[kind]
        for keys in settings:
            if self.has(*keys):
                continue
            key = next((k for k in keys if k in published), None)
            if key is None:
                self.refuse(keys[0], f"is not given, and model_type {kind!r} "
                            f"has no published default for it")
            if published[key] != FIXED[key]:
                self.refuse(keys[0], f"is not given: model_type {kind!r} "
                            f"then computes {published[key]!r}, the program "
                            f"only {FIXED[key]!r}")
        for k in self.cfg:
            if k not in self.used:
                self.refuse(k, "is not a key the program maps")


def from_config(cfg: dict):
    """The ``ModelConfig`` that runs the model ``cfg`` describes; raises
    ``Refused`` naming the first key it cannot map exactly."""
    from repro.configs.base import ModelConfig, MoEConfig
    r = _Reader(cfg)
    if cfg.get("model_type", "llama") not in DEFAULTS:
        r.refuse("model_type", f"is {cfg['model_type']!r}, whose published "
                 f"defaults are not tabled here; known: {sorted(DEFAULTS)}")
    if "mamba_dt_rank" in cfg:
        r.refuse("mamba_dt_rank", "is Mamba-1's low-rank dt projection; the "
                 "program's mixer is Mamba-2 (SSD), with one dt per head")
    d = int(r.get("hidden_size"))
    attn = r.has("num_attention_heads")
    ssm = _ssm(r, d, attn) if r.has(
        "mamba_d_state", "ssm_state_size", "state_size") else None

    heads = kv = hd = 0
    attn_every = 0
    rope_theta = 10000.0
    window, global_every = None, 0
    if attn:
        heads = int(r.get("num_attention_heads"))
        kv = int(r.get("num_key_value_heads", default=heads))
        hd = r.get("head_dim", default=None)
        if hd is None:
            if d % heads:
                r.refuse("num_attention_heads", f"does not divide "
                         f"hidden_size {d}, and no head_dim is given")
            hd = d // heads
        hd = int(hd)
        if not r.has("rope_theta"):
            r.refuse("rope_theta", "is not given, as for attention without "
                     "positions; the program's attention always rotates")
        rope_theta = float(r.get("rope_theta"))
        window = r.get("sliding_window", default=None)
        if window is not None:
            window = int(window)
            global_every = int(r.get("sliding_window_pattern", default=0))
        if ssm is not None:
            attn_every = r.every("attn_layer_period", "attn_layer_offset")

    moe, moe_every = None, 1
    n_experts = int(r.get("num_experts", "num_local_experts", default=0))
    top_k = int(r.get("num_experts_per_tok", default=0))
    if n_experts:
        width = r.get("moe_intermediate_size", default=None)
        if width is None:
            width = r.get("intermediate_size")
        moe = MoEConfig(n_experts=n_experts, top_k=top_k,
                        d_ff_expert=int(width))
        if r.has("expert_layer_period", "expert_layer_offset"):
            moe_every = r.every("expert_layer_period", "expert_layer_offset")
    elif top_k:
        r.refuse("num_experts_per_tok", f"is {top_k} with no experts")

    # Every layer without experts has a dense FFN, except in a model of
    # Mamba-2 layers alone, whose layers have none.
    dense_ffn = moe is None or moe_every > 1
    d_ff = 0
    if dense_ffn and attn:
        d_ff = int(r.get("intermediate_size"))
    elif dense_ffn and r.has("intermediate_size"):
        r.refuse("intermediate_size", "is given, but the program's Mamba-2 "
                 "layers without attention have no FFN")
    elif r.has("intermediate_size"):
        r.get("intermediate_size")   # the experts' width, or no layer's

    family = ("hybrid" if ssm is not None and attn else
              "ssm" if ssm is not None else
              "moe" if moe is not None else "dense")
    prec = r.get("precision")
    out = ModelConfig(
        name=cfg["name"], family=family,
        n_layers=int(r.get("num_hidden_layers")), d_model=d,
        n_heads=heads, n_kv_heads=kv, head_dim=hd, d_ff=d_ff,
        vocab_size=int(r.get("vocab_size")), rope_theta=rope_theta,
        norm_eps=float(r.get("rms_norm_eps")),
        attn_window=window, global_attn_every=global_every,
        moe=moe, moe_every=moe_every, ssm=ssm, attn_every=attn_every,
        tie_embeddings=bool(r.get("tie_word_embeddings")),
        param_dtype=prec["params"], compute_dtype=prec["compute"],
        source=cfg["source"])
    r.check_rest(_ALWAYS + (_ATTENTION if attn else ())
                 + (_FFN if d_ff or moe is not None else ())
                 + (_EXPERTS if moe is not None else ())
                 + (_MIXER if ssm is not None else ()))
    return out


def _ssm(r: _Reader, d: int, attn: bool):
    """The Mamba-2 mixer's sizes. A hybrid names them ``mamba_*``; a model
    of Mamba-2 layers alone may use ``Mamba2Config``'s own keys, where
    ``head_dim`` is the mixer's."""
    from repro.configs.base import SSMConfig
    head_keys = ("mamba_d_head", "mamba_head_dim") + (
        () if attn else ("head_dim",))
    expand = int(r.get("mamba_expand", "expand"))
    head = int(r.get(*head_keys))
    if (expand * d) % head:
        r.refuse(r.given(*head_keys), f"{head} does not divide the mixer's "
                 f"width {expand * d}")
    out = SSMConfig(
        d_state=int(r.get("mamba_d_state", "ssm_state_size", "state_size")),
        expand=expand, head_dim=head,
        conv_width=int(r.get("mamba_d_conv", "conv_kernel")),
        chunk_size=int(r.get("mamba_chunk_size", "chunk_size",
                             default=SSMConfig.chunk_size)))
    heads_keys = ("mamba_n_heads", "mamba_num_heads") + (
        () if attn else ("num_heads",))
    n = r.get(*heads_keys, default=None)
    if n is not None and int(n) != out.n_heads(d):
        r.refuse(r.given(*heads_keys), f"is {n}; the mixer's width over its "
                 f"head size gives {out.n_heads(d)}")
    return out
