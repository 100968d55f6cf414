"""Launch helpers: mesh and context builders, the compile cache, and the
recsys cell builder."""
