"""Vector-search service facade: backend-routed SquashIndex queries."""

from repro.serve.vector_service import ServiceConfig, VectorSearchService

__all__ = ["ServiceConfig", "VectorSearchService"]
