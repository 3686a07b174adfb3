"""Launch helpers: the ``(data, model)`` host mesh and the compile cache."""
