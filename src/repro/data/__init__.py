from repro.data.pipeline import insert_stream, make_clustered, query_stream

__all__ = ["insert_stream", "make_clustered", "query_stream"]
