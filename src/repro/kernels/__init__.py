# Pallas kernels a benchmark cell runs (server_plane.py) and their
# pure-jnp oracles (ref.py). A kernel stays only while a cell runs it.
