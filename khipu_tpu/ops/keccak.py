"""Batched Keccak-256 dispatcher: the Pallas kernel on TPU, the jnp
sponge on CPU (tests).

The public hashing entry point for the framework (trie commit, fast-sync
snapshot verify, content addressing). Replaces the reference's scalar
JVM sponge (khipu-base/.../crypto/hash/KeccakCore.scala) with batched
device execution; parity enforced against the scalar oracle in tests.
"""

from __future__ import annotations

from typing import List, Sequence

from khipu_tpu import device
from khipu_tpu.ops.keccak_jnp import keccak256_batch_jnp


def keccak256_batch(messages: Sequence[bytes], impl: str = "auto") -> List[bytes]:
    """Hash a batch of byte strings to 32-byte Keccak-256 digests.

    impl: "auto" (pallas on TPU, jnp on CPU), "jnp", or "pallas".
    """
    if impl == "auto":
        impl = "pallas" if device.platform() == "tpu" else "jnp"
    if impl == "pallas":
        from khipu_tpu.ops.keccak_pallas import keccak256_batch_pallas

        return keccak256_batch_pallas(messages)
    if impl == "jnp":
        return keccak256_batch_jnp(messages)
    raise ValueError(f"unknown keccak impl {impl!r}")
