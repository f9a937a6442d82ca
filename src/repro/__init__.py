"""repro: COALA reproduction framework (compression + serving + training)."""
