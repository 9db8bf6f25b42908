"""repro — DF* PageRank dynamic-graph framework on JAX (TPU-targeted).

x64 is enabled globally: the paper (§5.1.2) uses 64-bit floats for vertex
ranks with iteration tolerance 1e-10, which is unrepresentable in f32; the
graph substrate also packs (src,dst) into int64 keys.  All model code passes
explicit dtypes (bf16/f32/int32) so LM/GNN/recsys paths are unaffected.
"""
import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"
