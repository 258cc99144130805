"""Jitted public wrappers for the kernel layer.

``REPRO_USE_PALLAS=1`` selects the Pallas kernels; the default is the
pure-jnp reference path, which lowers to identical HLO shapes for the
roofline dry-run.  A Pallas kernel runs in the interpreter on the CPU
backend and compiled everywhere else (:func:`interpret_mode`); there is
no switch to interpret on an accelerator.  A kernel that cannot compile
for the TPU is refused there with an error, never interpreted and never
swapped for its reference.
"""
from __future__ import annotations

import os

import jax

from . import ref as _ref
from .backend import interpret_mode

_USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"

#: Pallas kernels the TPU compiler refuses, with the reason.
TPU_REFUSED = {
    "searchsorted_segments": (
        "the binary search gathers from the whole adjacency array, and "
        "Mosaic lowers only gathers within one 128-lane vreg row"),
}


def use_pallas() -> bool:
    return _USE_PALLAS


def _pallas(name: str) -> bool:
    """Whether kernel ``name`` runs as Pallas; raises where it is requested
    but cannot compile for the backend."""
    if not _USE_PALLAS:
        return False
    if name in TPU_REFUSED and jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"REPRO_USE_PALLAS=1: the {name} Pallas kernel does not compile "
            f"for the TPU ({TPU_REFUSED[name]}); unset REPRO_USE_PALLAS to "
            "run the jnp path")
    return True


def searchsorted_segments(values, lo, hi, queries, n_iter: int,
                          unroll: bool = False):
    if _pallas("searchsorted_segments"):
        from .searchsorted import searchsorted_segments_pallas
        return searchsorted_segments_pallas(values, lo, hi, queries,
                                            n_iter=n_iter,
                                            interpret=interpret_mode())
    return _ref.searchsorted_segments_ref(values, lo, hi, queries,
                                          n_iter=n_iter, unroll=unroll)


def bitset_intersect_count(a_words, b_words):
    if _pallas("bitset_intersect_count"):
        from .intersect_bitset import bitset_intersect_count_pallas
        return bitset_intersect_count_pallas(a_words, b_words,
                                             interpret=interpret_mode())
    return _ref.bitset_intersect_count_ref(a_words, b_words)


def bitset_member_count(words, b, b_len):
    if _pallas("bitset_member_count"):
        from .intersect_bitset import bitset_member_count_pallas
        return bitset_member_count_pallas(words, b, b_len,
                                          interpret=interpret_mode())
    return _ref.bitset_member_count_ref(words, b, b_len)


def flash_attention(q, k, v, causal: bool = True, scale=None):
    if _pallas("flash_attention"):
        from .flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      interpret=interpret_mode())
    return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
