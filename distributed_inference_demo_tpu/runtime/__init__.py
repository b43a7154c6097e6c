from .engine import InferenceEngine, GenerationResult
from .elastic import ElasticHeader, ElasticStageRuntime, ElasticWorker
from .speculative import SpeculativeEngine, SpecStats
from .batching import ContinuousBatchingEngine

__all__ = ["InferenceEngine", "GenerationResult", "ElasticHeader",
           "ElasticStageRuntime", "ElasticWorker", "SpeculativeEngine",
           "SpecStats", "ContinuousBatchingEngine"]
