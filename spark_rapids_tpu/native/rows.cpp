// Row-wise copies between Arrow's flat var-width buffers and the
// device's padded [rows, width] matrices (columnar/batch.py
// _strings_to_matrix): one memcpy and one memset a row, no index matrix,
// no mask, no pass over the matrix to zero it first.

#include <cstdint>
#include <cstring>

namespace {

template <typename Offset>
void pad_rows(const uint8_t* data, const Offset* starts, const int32_t* lens,
              int64_t n, int64_t width, uint8_t* out, int64_t rows) {
    for (int64_t i = 0; i < n; ++i) {
        uint8_t* row = out + i * width;
        const size_t len = lens[i] > 0 ? static_cast<size_t>(lens[i]) : 0;
        if (len) std::memcpy(row, data + starts[i], len);
        std::memset(row + len, 0, static_cast<size_t>(width) - len);
    }
    if (rows > n)
        std::memset(out + n * width, 0,
                    static_cast<size_t>((rows - n) * width));
}

}  // namespace

extern "C" {

// out: rows * width bytes, not initialized: every byte is written once
// (row i's bytes, then zeros to the width; rows n.. all zeros).
// lens[i] <= width (the caller checks); starts[i]: where row i begins in
// data.
void pad_rows32(const uint8_t* data, const int32_t* starts,
                const int32_t* lens, int64_t n, int64_t width,
                uint8_t* out, int64_t rows) {
    pad_rows(data, starts, lens, n, width, out, rows);
}

void pad_rows64(const uint8_t* data, const int64_t* starts,
                const int32_t* lens, int64_t n, int64_t width,
                uint8_t* out, int64_t rows) {
    pad_rows(data, starts, lens, n, width, out, rows);
}

}  // extern "C"
