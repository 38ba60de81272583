"""One module per graph model, found by the configuration's
``graph.model``.

A model module defines ``draw(rng, num_nodes, params)``, which returns
the undirected edges ``a < b`` (int64), their weights and the node
weights (float32), all drawn from the NumPy generator ``rng``;
``params`` is the configuration's ``graph`` object.
"""
