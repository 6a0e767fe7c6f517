"""Reference-twin registry for the pallas kernels.

Every public pallas kernel entry point must name a pure-jnp oracle here
— the function the parity tests (and the `impl="jnp"` dispatch path in
``ops.py``) compare it against.  reprolint's ``ref-twin`` rule fails the
build when a new kernel lands without an entry, or an entry points at a
function that no longer exists.

Keys are ``"<kernel module>:<public function>"``; values are
``"jnp_impl:<fn>"`` or ``"ref:<fn>"``.  The dict must stay a pure
literal — the linter reads it with ``ast.literal_eval`` without
importing jax.
"""

from __future__ import annotations

REFERENCE_TWINS = {
    # flash prefill/decode attention <-> O(S^2) masked reference
    "flash_attention:flash_attention": "ref:attention_ref",
    # MemCom compressor cross-attention (queries = memory slots)
    "memcom_xattn:memcom_xattn": "ref:memcom_xattn_ref",
    # grouped matmul behind the MoE dispatch
    "moe_gmm:gmm": "ref:gmm_ref",
    # paged decode attention <-> streaming jnp block-table walk
    "paged_attention:paged_flash_decode": "jnp_impl:paged_decode_attention_lengths",
    # absorbed-MLA decode over a paged latent pool (one latent head; the
    # jnp path gathers each slot's view and runs the length-masked decode)
    "paged_attention:paged_latent_decode": "jnp_impl:decode_attention_lengths",
    # dense-stripe decode (the paged kernel over a reshaped cache)
    "paged_attention:dense_flash_decode": "jnp_impl:decode_attention_lengths",
    # mamba2 state-space chunked scan
    "ssd_scan:ssd": "ref:ssd_ref",
}


def resolve(key: str):
    """Import and return the twin callable for ``key`` (test helper —
    the linter never calls this; it parses the literal above)."""
    target = REFERENCE_TWINS[key]
    modname, fn = target.split(":")
    if modname == "jnp_impl":
        from repro.kernels import jnp_impl as mod
    elif modname == "ref":
        from repro.kernels import ref as mod
    else:  # pragma: no cover - registry validated by reprolint
        raise ValueError(f"unknown twin module {modname!r}")
    return getattr(mod, fn)
