"""What the training and serving launchers share: the model flags and
the persistent compile cache.

``add_model_args`` / ``model_config`` give both launchers the same
``--arch`` / ``--reduced`` / ``--n-layers`` flags.  ``--n-layers`` cuts
depth only: every width (d_model, heads, d_ff, vocabulary) stays the
published one, and ``layer_pattern``'s assert refuses a depth that is
not a whole number of layer-pattern periods.

``enable_compile_cache`` places JAX's persistent compilation cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this leaves
it alone; otherwise the cache sits at ``<repo>/.jax_cache`` (off on the
CPU backend).  The path
is fixed because it is part of the cache key: a directory that moves
never hits.
"""
from __future__ import annotations

import dataclasses
import os

import jax

from repro.configs import ARCHS, get_config, layer_pattern, smoke_variant

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache(profiled: bool = False) -> str:
    """Turn on the persistent compile cache; returns its directory, or
    "off" on the CPU backend, where the tests run: CPU programs compile
    in seconds, and XLA:CPU warns about host features on every reload.
    Call it after ``jax.distributed.initialize``: it asks JAX for the
    backend.  ``profiled``: a run whose trace will be read keys the cache
    on op metadata too, since an executable loaded from the cache keeps
    the metadata it was compiled with, and so the scopes of whatever
    code compiled it first."""
    if profiled:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.default_backend() == "cpu":
        return "off"
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def add_model_args(ap, default_arch: str) -> None:
    ap.add_argument("--arch", default=default_arch, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="cut depth to N layers at the published widths "
                         "(whole periods of the layer pattern; 0 = all)")


def model_config(args, tag: str):
    """The ModelConfig the flags select; prints a depth cut."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = smoke_variant(cfg)
    if args.n_layers:
        full = cfg.n_layers
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
        layer_pattern(cfg)
        print(f"[{tag}] {cfg.name}: depth cut to {cfg.n_layers} of {full} "
              f"layers, widths unchanged")
    return cfg
