"""PiP-MColl's multi-object collectives for JAX on TPU, and the training
and serving paths that put them on the critical path."""
import jax as _jax

# A profile attributes each device operation by its op metadata: the
# layers' named scopes and the source lines. JAX's persistent compilation
# cache leaves that metadata out of its key by default, so an executable
# loaded from the cache would carry the metadata of whichever build
# compiled it first, and a profile would name that build's scopes and
# lines. Keying the cache on metadata keeps profiles true to the code that
# runs, at the price of a compile when only a source line moves. It is set
# here, on import, and not in ``launch/cache.enable()`` alone, because
# entry points that place the cache through ``JAX_COMPILATION_CACHE_DIR``
# without the launcher share one directory between builds too.
_jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
