"""One module per table: schema, generator, query, lower-bound bytes and
the plain numpy reference. A configuration names its module by
``dataset``."""
