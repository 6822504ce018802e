"""repro.launch — mesh, tenant zoo, compile cache, train/serve entry points."""
