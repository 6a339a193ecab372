"""Launch: mesh construction, TPU tiling targets, the compile cache and
post-compile HLO analysis."""
