"""``dense_ln``: uniform pre-LayerNorm decoder blocks, each rotary
multi-head (or grouped) attention and a dense feed-forward (SwiGLU or
GELU), one block kind in every layer; the program's ``uniform_segments``
stack."""
