"""Window drivers, named by a traffic file's ``driver`` key."""
