// Pure-C++ serving smoke binary: no Python linked or embedded.
//
//   predictor_smoke <artifact-base-path> <pjrt-plugin.so>
//
// Loads the artifact through the same C ABI a C/Go/Rust embedder would
// use, fills every input with a deterministic ramp, runs one
// ZeroCopy-style inference, and prints per-output checksums. The CI gate
// runs it against the mock plugin (mechanics); on a TPU host, point it
// at libtpu for the real thing. Reference analog: the
// standalone predictor demos under
// `paddle/fluid/inference/api/demo_ci/`.

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

extern "C" {
void* ptp_create(const char* artifact, const char* plugin, char* err,
                 int errlen);
int ptp_num_inputs(void* h);
int ptp_num_outputs(void* h);
int ptp_io_rank(void* h, int is_input, int i);
void ptp_io_shape(void* h, int is_input, int i, int64_t* dims);
const char* ptp_io_dtype(void* h, int is_input, int i);
int64_t ptp_io_bytes(void* h, int is_input, int i);
int ptp_run(void* h, const void** in, void** out, char* err, int errlen);
void ptp_destroy(void* h);
}

int main(int argc, char** argv) {
  if (argc != 3) {
    std::fprintf(stderr,
                 "usage: %s <artifact-base-path> <pjrt-plugin.so>\n",
                 argv[0]);
    return 2;
  }
  char err[1024] = {0};
  void* h = ptp_create(argv[1], argv[2], err, sizeof(err));
  if (!h) {
    std::fprintf(stderr, "create failed: %s\n", err);
    return 1;
  }
  int ni = ptp_num_inputs(h), no = ptp_num_outputs(h);
  std::printf("inputs=%d outputs=%d\n", ni, no);

  std::vector<std::vector<char>> in_store(ni), out_store(no);
  std::vector<const void*> in_ptrs(ni);
  std::vector<void*> out_ptrs(no);
  for (int i = 0; i < ni; ++i) {
    int64_t nbytes = ptp_io_bytes(h, 1, i);
    in_store[i].resize((size_t)nbytes);
    // deterministic byte ramp: dtype-agnostic, reproducible
    for (int64_t j = 0; j < nbytes; ++j) {
      in_store[i][(size_t)j] = (char)((j * 7 + i * 13) % 61);
    }
    in_ptrs[i] = in_store[i].data();
    int rank = ptp_io_rank(h, 1, i);
    std::vector<int64_t> dims((size_t)rank);
    ptp_io_shape(h, 1, i, dims.data());
    std::printf("input %d dtype=%s bytes=%lld dims=[", i,
                ptp_io_dtype(h, 1, i), (long long)nbytes);
    for (int r = 0; r < rank; ++r) {
      std::printf("%s%lld", r ? "," : "", (long long)dims[(size_t)r]);
    }
    std::printf("]\n");
  }
  for (int i = 0; i < no; ++i) {
    out_store[i].resize((size_t)ptp_io_bytes(h, 0, i));
    out_ptrs[i] = out_store[i].data();
  }

  int rc = ptp_run(h, in_ptrs.data(), out_ptrs.data(), err, sizeof(err));
  if (rc != 0) {
    std::fprintf(stderr, "run failed rc=%d: %s\n", rc, err);
    ptp_destroy(h);
    return 1;
  }
  for (int i = 0; i < no; ++i) {
    uint64_t sum = 0;
    for (char c : out_store[i]) sum = sum * 131 + (unsigned char)c;
    std::printf("output %d dtype=%s bytes=%zu checksum=%llu\n", i,
                ptp_io_dtype(h, 0, i), out_store[i].size(),
                (unsigned long long)sum);
  }
  ptp_destroy(h);
  std::printf("OK\n");
  return 0;
}
